"""A decode step of a model with routed experts against its memory
roofline, %: the bytes the step must read (`lib/counts_pattern.py`: the
weights outside the experts once, the DISTINCT experts the step's tokens
chose, from the program's own counter, and the live cache, a row's whole
depth in the full-attention layers and `min(depth, window)` in the
sliding ones) over the chip's bandwidth, against the decode program's
device time.  None where the program counts no experts."""
from benchmark.lib import counts_pattern
from benchmark.readers import module_time


def read(ctx, match: str):
    runs = module_time.picked_runs(ctx, match, "most_run")
    c = ctx.counters
    steps = c.get("device_steps")
    if (not runs or not steps or not c.get("moe_layer_steps")
            or ctx.config.get("family") != "pattern_moe_lm"):
        return None
    least_s = counts_pattern.decode_step_bytes(
        ctx.config, c["experts_hit_sum"] / steps,
        c["live_tokens_sum"] / steps, c["ring_tokens_sum"] / steps,
        ctx.config["serve"]["weights_dtype"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
