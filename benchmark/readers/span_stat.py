"""A statistic over the program's spans of one name (see
`reduce/program_spans.py`) that lie inside the traced window.

The values are the spans' durations in ms, or, with `arg`, one numeric
argument of each span times `scale` (`queue_wait_us` with 1e-3 is ms).
`stat` is "mean_ms" (the mean) or "p<q>" (a percentile, "p50").  With
`without`, only the spans that hold no span of that name count: an
`hvd.serve.step` without an `hvd.serve.prefill` inside admitted nothing.
None when nothing is left to read."""
from benchmark.lib.stats import pct
from benchmark.reduce import program_spans


def values(spans, lo, hi, span, arg=None, scale=1.0, without=None):
    picked = program_spans.named(spans, span, lo, hi)
    if without is not None:
        starts = [s.start_s for s in spans if s.name == without]
        picked = [s for s in picked
                  if not any(s.start_s <= x <= s.end_s for x in starts)]
    if arg is None:
        return [1e3 * (s.end_s - s.start_s) for s in picked]
    return [float(s.stats[arg]) * scale for s in picked if arg in s.stats]


def read(ctx, span: str, stat: str, arg=None, scale: float = 1.0,
         without=None):
    t = ctx.trace
    if t is None:
        return None
    xs = values(program_spans.of_cell(ctx.cell["name"]), t.lo, t.hi,
                span, arg, scale, without)
    if not xs:
        return None
    if stat == "mean_ms":
        return sum(xs) / len(xs)
    if stat.startswith("p"):
        return pct(xs, float(stat[1:]))
    raise ValueError(f"span_stat: no statistic {stat!r}")
