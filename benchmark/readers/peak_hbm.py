"""Peak device memory on the fullest chip, GiB: the live buffers' peak
(`peak_bytes_in_use`, which leaves out programs' temporaries) plus what
the runtime reserved for the loaded programs' temporaries
(`peak_bytes_reserved`); see `lib/harness.py`, `memory_peak`."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2 ** 30
