"""A percentile of one of the run's sample lists."""
from benchmark.lib.stats import pct


def read(ctx, samples: str, q: float):
    xs = ctx.samples.get(samples)
    if not xs:
        return None
    return pct(xs, q)
