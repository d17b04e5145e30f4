"""Device time of compiled programs, told apart by module name.

`match` is a prefix of the module's name.  The server jits its prefill
and its decode step from lambdas, so both are `jit__lambda(<fingerprint>)`: `pick`
"most_run" keeps the program that ran most often (the decode step, once
a server step) and "others" the rest (one prefill program per shape).
`stat`: "mean_ms" per run, or "share_of_busy" in %."""


def picked_runs(ctx, match: str, pick: str):
    t = ctx.trace
    if t is None:
        return None
    runs = {n: r for n, r in t.module_runs().items() if n.startswith(match)}
    if not runs:
        return None
    top = max(runs, key=lambda n: len(runs[n]))
    if pick == "most_run":
        return runs[top]
    if pick == "others":
        return [x for n, r in runs.items() if n != top for x in r]
    return [x for r in runs.values() for x in r]


def read(ctx, match: str, pick: str = "all", stat: str = "mean_ms"):
    runs = picked_runs(ctx, match, pick)
    if runs is None:
        return None
    if stat == "share_of_busy":
        return 100.0 * sum(runs) / ctx.trace.busy_s
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
