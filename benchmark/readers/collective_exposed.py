"""Share of the traced window in which only collectives ran, %."""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.chips < 2:
        return None
    return 100.0 * t.exposed_collective_s() / t.window_s
