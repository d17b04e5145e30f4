"""The routed experts' grouped-product kernel against its roofline, %,
inside the decode step: the device time of the operations whose name
matches `op` (the kernel's calls) that ran inside a run of the decode
program (the module of prefix `match` run most often), a run, against
the least time the chip could take for what those calls must do
(`lib/counts_pattern.py`): the larger of the bytes of the distinct
experts chosen, from the program's counter, with the rows in and out,
over the chip's bandwidth, and the routed pairs' operations over its
peak.  None where no such operation ran or the program counts no
experts."""
import re

from benchmark.lib import counts_pattern


def read(ctx, match: str, op: str):
    t, c = ctx.trace, ctx.counters
    steps = c.get("device_steps")
    if (t is None or not steps or not c.get("moe_layer_steps")
            or ctx.config.get("family") != "pattern_moe_lm"):
        return None
    chip = t.chips[0]
    runs = {}
    for name, s, e in chip.modules:
        if name.startswith(match) and s >= t.lo and e <= t.hi:
            runs.setdefault(name, []).append((s, e))
    if not runs:
        return None
    steps_run = sorted(max(runs.values(), key=len))
    pat = re.compile(op)
    calls = sorted((s, e) for name, s, e in chip.ops if pat.fullmatch(name))
    busy, i = 0.0, 0
    for lo, hi in steps_run:
        while i < len(calls) and calls[i][0] < lo:
            i += 1
        while i < len(calls) and calls[i][0] < hi:
            busy += calls[i][1] - calls[i][0]
            i += 1
    if not busy:
        return None
    m = ctx.config
    pairs = (c["occupancy_sum"] / steps * ctx.traffic["server"]["max_batch"]
             * m["num_experts_per_tok"])
    least_s = max(
        counts_pattern.grouped_product_bytes(
            m, c["experts_hit_sum"] / steps, pairs,
            m["serve"]["weights_dtype"]) / ctx.peaks["hbm_bytes_per_s"],
        counts_pattern.sparse_layers(m) * counts_pattern.expert_flops(
            m, pairs) / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (busy / len(steps_run))
