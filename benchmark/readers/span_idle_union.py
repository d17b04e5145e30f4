"""Share of the traced window, in %, in which chip 0 ran no operation
while the program was inside ANY of the named spans: `span_idle` over
the union of their intervals.

`hvd.serve.launch` and `hvd.serve.fetch` border the same busy stretch,
the decode program's run, so each session's alignment of the host's
clock with the device's moves idle from one to the other (`span_idle`'s
docstring; ten points between two runs of one program).  What lies under
the two together is the device's wait for the host around a step, and
the alignment does not touch it: spans that do not overlap give the sum
of their `span_idle` readings, whatever the offset.  None when the run
has none of the spans."""
from benchmark.readers import span_idle
from benchmark.reduce import program_spans


def read(ctx, spans):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    mine = [(s.start_s, s.end_s)
            for s in program_spans.of_cell(ctx.cell["name"])
            if s.name in spans]
    if not mine:
        return None
    return 100.0 * span_idle.idle_inside(t.busy(t.chips[0]), mine,
                                         t.lo, t.hi) / t.window_s
