"""The program's own spans in a traced run: every event of the host plane
whose name starts with `hvd.`, which is what `horovod_tpu/utils/timeline
.span` writes (`hvd.<category>.<name>`, a `jax.profiler.TraceAnnotation`
with the span's arguments as the event's stats).  They lie in the same
`.xplane.pb` as the device's operations and the harness's `bench.*`
spans, on the same clock.

`xplane.Reduced` keeps only the `bench.*` spans and `ReadContext` holds
no path, so this module finds the file the way the harness writes it:
`<checkout>/.bench_trace/<cell>/**/*.xplane.pb`, the checkout being the
parent of `benchmark/`.  The harness removes that directory only after
the readers have run.  A program with no such span (the parent of the PR
that added them, a training cell) gives an empty list, and so does a run
that was not traced.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, NamedTuple, Tuple

PREFIX = "hvd."
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Span(NamedTuple):
    name: str
    start_s: float
    end_s: float
    stats: Dict


def read_file(path: str) -> List[Span]:
    """The `hvd.*` events of one `.xplane.pb`, in order of start."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(
                        ev.name, ev.start_ns / 1e9,
                        (ev.start_ns + ev.duration_ns) / 1e9,
                        {k: v for k, v in ev.stats}))
    out.sort(key=lambda s: (s.start_s, -s.end_s))
    return out


@functools.lru_cache(maxsize=1)
def _read_cached(path: str, mtime_ns: int) -> Tuple[Span, ...]:
    return tuple(read_file(path))


def of_cell(cell_name: str, checkout: str = CHECKOUT) -> Tuple[Span, ...]:
    """The spans of the traced run of `cell_name` that is being reduced
    now; nothing when there is no trace.  One metric after another asks,
    so the newest file is read once."""
    paths = sorted(glob.glob(os.path.join(
        checkout, ".bench_trace", cell_name, "**", "*.xplane.pb"),
        recursive=True))
    if not paths:
        return ()
    return _read_cached(paths[-1], os.stat(paths[-1]).st_mtime_ns)


def named(spans, name: str, lo: float, hi: float) -> List[Span]:
    """The spans called `name` that lie inside [lo, hi]."""
    return [s for s in spans
            if s.name == name and s.start_s >= lo and s.end_s <= hi]
