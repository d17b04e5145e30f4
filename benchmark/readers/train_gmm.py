"""The routed experts' grouped products inside a TRAIN step, from the
device trace: the operations whose name matches `op` (the kernels' calls:
`gmm`, `gmm.<n>`, `tgmm`, `tgmm.<n>`) that ran inside a run of the train
step's program (the module of prefix `match` run most often).

`stat` "roofline": %, the least time the chip could take for the nine
grouped products of a step (`lib/counts_conv_moe.py`: three forward, and
of each the rows' and the weights' gradient; the forward products a
recomputing step runs again are NOT counted) over the pairs the program's
counter gives: the larger of their operations over the chip's peak and
their bytes over its bandwidth, against those operations' device time a
step.  "share_of_step": %, that device time over the program's.

None where the window counted no routed pairs (a program without the
counter) or the trace holds no run of the program.  A step that counted
pairs and ran no such operation is an error, not silence: the kernels'
names are part of the yardstick."""
import re

from benchmark.lib import counts_conv_moe


def read(ctx, match: str, op: str, stat: str):
    t, c = ctx.trace, ctx.counters
    if (t is None or not c.get("moe_layer_steps")
            or not c.get("pairs_here_sum")
            or ctx.config.get("family") != "conv_moe_lm"):
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
        raise RuntimeError(
            f"the train step counted routed pairs and ran no operation "
            f"named {op!r}")
    if stat == "share_of_step":
        return 100.0 * busy / sum(e - s for s, e in steps_run)
    m = ctx.config
    steps = c["moe_layer_steps"] / counts_conv_moe.sparse_layers(m)
    pairs = c["pairs_here_sum"] / steps            # all sparse layers
    least_s = max(
        counts_conv_moe.grouped_flops(m, pairs)
        / ctx.peaks["bf16_flops_per_s"],
        counts_conv_moe.grouped_bytes(m, pairs, m["train"]["compute_dtype"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (busy / len(steps_run))
