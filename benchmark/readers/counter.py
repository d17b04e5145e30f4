"""A counter's change over the window."""


def read(ctx, counter: str):
    return ctx.counters.get(counter)
