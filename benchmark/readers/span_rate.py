"""The mean, over the program's spans of one name inside the traced
window, of a span's length in ms over one of its numeric arguments
times `scale`: `hvd.serve.prefill` over `prompt_tokens` at 1e-3 is ms a
thousand prompt tokens.  Spans without the argument, or with it at 0,
are left out; None when nothing is left to read."""
from benchmark.reduce import program_spans


def read(ctx, span: str, arg: str, scale: float = 1.0):
    t = ctx.trace
    if t is None:
        return None
    xs = [1e3 * (s.end_s - s.start_s) / (float(s.stats[arg]) * scale)
          for s in program_spans.named(
              program_spans.of_cell(ctx.cell["name"]), span, t.lo, t.hi)
          if s.stats.get(arg)]
    return sum(xs) / len(xs) if xs else None
