"""One counter over another, times `scale` (100 for a share in %)."""


def read(ctx, num: str, den: str, scale: float = 1.0):
    if not ctx.counters.get(den):
        return None
    return scale * ctx.counters.get(num, 0.0) / ctx.counters[den]
