"""A decode step's share of its memory roofline, %: the bytes the step
must read (weights once, the live rows of the cache, counted by
benchmark/lib/counts.py from the tokens live at each step) over the
chip's bandwidth, against the decode program's device time."""
from benchmark.lib import counts
from benchmark.readers import module_time


def read(ctx, match: str):
    runs = module_time.picked_runs(ctx, match, "most_run")
    steps = ctx.counters.get("device_steps")
    if not runs or not steps:
        return None
    live = ctx.counters["live_tokens_sum"] / steps
    least_s = (counts.decode_step_bytes(ctx.config, live)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(runs) / len(runs))
