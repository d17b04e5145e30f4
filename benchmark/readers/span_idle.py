"""Share of the traced window, in %, in which chip 0 ran no operation
while the program was inside the named span (`hvd.serve.fetch`, ...).

The idle intervals are those `device_idle` counts
(`xplane.gaps(busy of chip 0, lo, hi)`); each is *intersected* with the
span's intervals, so a gap that straddles two phases is split between
them, and phases that partition a step add up to the idle inside it.
None when the run has no such span: an untraced run, a program without
spans, a cell that never enters it.

The profiler aligns the host's and the device's clocks anew in every
session, to about a millisecond (chip runs, PERF.md section 5).  A phase
that lies wholly inside an idle gap does not feel that; two phases that
border the same busy stretch (`launch` before the decode program,
`fetch` after it) trade that much idle between them: read their union
(`span_idle_union`; no metric lists either of the two alone)."""
from benchmark.reduce import program_spans, xplane


def intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_inside(busy, spans, lo, hi):
    """Seconds of [lo, hi] outside `busy` and inside `spans` (intervals,
    any order, clipped to the window here)."""
    inside = xplane.clip(xplane.merge(list(spans)), lo, hi)
    return xplane.total(intersect(xplane.gaps(busy, lo, hi), inside))


def read(ctx, span: str):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    mine = [(s.start_s, s.end_s)
            for s in program_spans.of_cell(ctx.cell["name"])
            if s.name == span]
    if not mine:
        return None
    return 100.0 * idle_inside(t.busy(t.chips[0]), mine, t.lo, t.hi) \
        / t.window_s
