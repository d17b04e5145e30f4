"""What `correct` compares in a training cell, shared by its runners.

A reading is {"losses": [l1, l2, l3], "grad_norms": {leaf: n},
"delta_norms": {leaf: n}}: each step's loss, the norm of the first
gradient as the optimizer got it, and the norm of the parameters' change
over the steps, leaf by leaf.  Norms are compared by the worst leaf: the
gap between the two norms (not the norm of a difference) against the
reference's norm of that leaf or of the median leaf, whichever is larger,
since some gradients are all but zero.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

from benchmark.lib import counts
from benchmark.lib.harness import Check, WindowResult


def worst_leaf(got: Dict[str, float], want: Dict[str, float]) -> float:
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)


def compare(got: Dict, want: Dict, limits: Dict) -> List[Check]:
    """`limits["leaf_groups"]`, where a configuration has it, maps a
    group's name to substrings of its leaves' paths; the worst leaf is
    then taken, and limited, group by group (a limit per group under
    `grad_norm_rel` and `delta_norm_rel`); leaves in no group are not
    compared.  ResNet needs it: a batch norm's scale and bias are sums
    with heavy cancellation over every position of the batch, bf16
    rounding moves their norms by tens of percent (0.25 to 0.58 on the
    chip, against 0.9997 for the control), so no limit on them holds, and
    taken together with the kernels they would hide a fault in the
    convolutions (kernels read 0.015 to 0.033)."""
    checks = [
        Check(f"step {i + 1} loss against the reference's, relative",
              abs(a - b) / abs(b), limits["loss_rel"])
        for i, (a, b) in enumerate(zip(got["losses"], want["losses"]))]
    groups = limits.get("leaf_groups") or {"": ""}
    for key, what in (("grad_norms", "first gradient's norm"),
                      ("delta_norms", "parameters' change over the "
                                      "steps, norm")):
        for group, part in groups.items():
            parts = [part] if isinstance(part, str) else part
            ref = {k: v for k, v in want[key].items()
                   if any(p in k for p in parts)}
            limit = limits[key[:-1] + "_rel"]
            checks.append(Check(
                f"{what}, worst {group + ' ' if group else ''}leaf, "
                f"against the reference's",
                worst_leaf({k: got[key][k] for k in ref}, ref),
                limit[group] if group else limit))
    return checks


def timed_steps(ctx, seconds: float, dispatch, sync):
    """The training window: steps dispatched back to back, each ended by
    a `block_until_ready`, one step kept in flight so the device never
    waits for the host.  `dispatch()` enqueues a step and returns what to
    wait on; `sync(x)` waits.  Returns (steps, seconds, step ends)."""
    import collections
    import time
    pending = collections.deque()
    ends: List[float] = []
    t0 = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - t0
        if ctx.tracer:
            ctx.tracer.poll(elapsed)
        if elapsed >= seconds:
            break
        with ctx.span("train.dispatch"):
            pending.append(dispatch())
        if len(pending) >= 2:
            with ctx.span("train.sync"):
                sync(pending.popleft())
            ends.append(time.perf_counter())
    while pending:
        with ctx.span("train.sync"):
            sync(pending.popleft())
        ends.append(time.perf_counter())
    if ctx.tracer:
        ctx.tracer.stop()
    return len(ends), ends[-1] - t0, ends


def mfu_window(ctx, seconds: float, dispatch, batch: int,
               what: str) -> WindowResult:
    """The window of a training cell and its `train_mfu`: operations the
    forward and backward passes need per sample times samples a second,
    over the chips' bf16 peak."""
    import jax
    steps, secs, ends = timed_steps(ctx, seconds, dispatch,
                                    jax.block_until_ready)
    flops = counts.train_flops_per_sample(ctx.config, ctx.traffic)
    mfu = 100.0 * flops * batch * steps / secs / (
        len(ctx.devices) * ctx.peaks["bf16_flops_per_s"])
    step_ms = [1e3 * (b - a) for a, b in zip(ends, ends[1:])]
    print(f"window: {steps} steps of {batch} {what} in {secs:.3f} s, "
          f"{batch * steps / secs:.2f} samples/s, median step "
          f"{statistics.median(step_ms):.3f} ms")
    return WindowResult(attempted=steps, failed=0,
                        end_to_end={"train_mfu": mfu},
                        samples={"step_ms": step_ms})
