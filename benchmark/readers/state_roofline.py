"""A retention model's decode step against its memory roofline, %: the
bytes the step must move (weights once; the state and normaliser of the
rows active, read and written: `lib/counts_retention.py`, at the mean
number of active rows over the window's steps) over the chip's
bandwidth, against the decode program's device time."""
from benchmark.lib import counts_retention
from benchmark.readers import module_time


def read(ctx, match: str):
    runs = module_time.picked_runs(ctx, match, "most_run")
    steps = ctx.counters.get("device_steps")
    if (not runs or not steps
            or ctx.config.get("family") != "retention_lm"):
        return None
    rows = (ctx.counters["occupancy_sum"] / steps
            * ctx.traffic["server"]["max_batch"])
    sv = ctx.config["serve"]
    least_s = (counts_retention.decode_step_bytes(
        ctx.config, rows, sv["weights_dtype"], sv["state_dtype"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(runs) / len(runs))
