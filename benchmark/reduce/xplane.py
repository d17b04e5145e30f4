"""From a profiler trace (`.xplane.pb`) to what the readers need: the
intervals in which an operation ran on each chip, time by operation and
by compiled program, and the idle gaps with what the harness was doing
in each.  Read with nothing but JAX (`jax.profiler.ProfileData`).

In a TPU trace every chip is a plane `/device:TPU:<n>` whose line
"XLA Ops" holds one event per operation (a `while` and the operations of
its body both appear, so intervals are merged before they are summed) and
whose line "XLA Modules" holds one event per run of a compiled program.
The harness's own spans (`bench.*` TraceAnnotations) are on the host
plane's "python" line, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]          # seconds, start and end

WINDOW_SPAN = "bench.trace_window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_name(raw: str) -> str:
    """'%fusion.148 = bf16[...] fusion(...)' -> 'fusion.148'."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


@dataclasses.dataclass
class ChipTrace:
    ops: List[Tuple[str, float, float]]        # name, start, end
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Reduced:
    chips: List[ChipTrace]
    spans: List[Tuple[str, float, float]]      # harness spans
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy(self, chip: ChipTrace, keep=lambda name: True) -> List[Interval]:
        return clip(merge([(s, e) for n, s, e in chip.ops if keep(n)]),
                    self.lo, self.hi)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation on the device, mean over chips."""
        return sum(total(self.busy(c)) for c in self.chips) / len(self.chips)

    def exposed_collective_s(self) -> float:
        """Busy time in which only collectives ran, mean over chips."""
        out = 0.0
        for c in self.chips:
            everything = total(self.busy(c))
            compute = total(self.busy(
                c, lambda n: not COLLECTIVE.search(n)))
            out += everything - compute
        return out / len(self.chips)

    def module_runs(self, chip: int = 0) -> Dict[str, List[float]]:
        """Device seconds of each run of each compiled program, by its
        module name with its fingerprint: `jit_step(1648...)`."""
        out: Dict[str, List[float]] = {}
        for n, s, e in self.chips[chip].modules:
            if s >= self.lo and e <= self.hi:
                out.setdefault(n, []).append(e - s)
        return out

    def top_ops(self, k: int) -> List[List]:
        """The k operations with most device time on chip 0."""
        by: Dict[str, float] = {}
        for n, s, e in self.chips[0].ops:
            s, e = max(s, self.lo), min(e, self.hi)
            if e > s:
                by[n] = by.get(n, 0.0) + (e - s)
        return [[n, t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int) -> List[List]:
        """Idle time on chip 0 by the innermost harness span that was
        open at the middle of each gap."""
        by: Dict[str, float] = {}
        for s, e in gaps(self.busy(self.chips[0]), self.lo, self.hi):
            mid, name, width = (s + e) / 2, "(no span)", float("inf")
            for n, a, b in self.spans:
                if a <= mid <= b and b - a < width:
                    name, width = n, b - a
            by[name] = by.get(name, 0.0) + (e - s)
        return [[n, t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def reduce_file(path: str, n_chips: int) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips: Dict[int, ChipTrace] = {}
    spans: List[Tuple[str, float, float]] = []
    window = None
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = ChipTrace([], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip.ops = [(op_name(ev.name), ev.start_ns / 1e9,
                                 (ev.start_ns + ev.duration_ns) / 1e9)
                                for ev in line.events]
                elif line.name == "XLA Modules":
                    chip.modules = [(ev.name, ev.start_ns / 1e9,
                                     (ev.start_ns + ev.duration_ns) / 1e9)
                                    for ev in line.events]
            chips[int(m.group(1))] = chip
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        rec = (ev.name[len("bench."):], ev.start_ns / 1e9,
                               (ev.start_ns + ev.duration_ns) / 1e9)
                        if ev.name == WINDOW_SPAN:
                            window = rec
                        else:
                            spans.append(rec)
    used = [chips[i] for i in sorted(chips)][:n_chips]
    if not used or not any(c.ops for c in used):
        raise RuntimeError(f"no device operation in the trace {path}")
    if window is not None:
        lo, hi = window[1], window[2]
    else:
        lo = min(s for c in used for _, s, _ in c.ops)
        hi = max(e for c in used for _, _, e in c.ops)
    return Reduced(used, spans, lo, hi)


def reduce_dir(trace_dir: str, n_chips: int) -> Reduced:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(paths[-1], n_chips)
