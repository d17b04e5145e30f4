"""A decode step, or the grouped products inside it, against its
roofline, %, with the WORK read in the window the TIME is read in.

The program's spans say what each step did (`hvd.serve.launch`: `dstep`,
`rows`, `live_tokens`, `ring_tokens`; `hvd.serve.observe`: `dstep`,
`experts_hit` of the step whose sync it follows).  The steps launched
inside the traced `[lo, hi]` are taken, each with the `observe` of its
own `dstep` (so a server that keeps a step in flight pairs as one that
does not), and their mean work is set against the mean device time of
the decode program's runs inside the same `[lo, hi]` (the module of
prefix `match` run most often: `module_time.picked_runs`).  These are
the only serving rooflines: a counter summed over the whole 40 s window
over that time of its last 5 s reads too high when the traced batch is
lighter than the window's mean (PERF.md 6, PR 48).

`kind`, with the count it takes from `lib/counts*.py`:
  "decode"  weights once and the live tokens' cache (`decoder_lm`);
  "moe"     weights outside the experts, the distinct experts hit, the
            full layers' live tokens and the rings' (`pattern_moe_lm`);
  "gmm"     the operations named `op` inside the decode program's runs
            against the larger of the chosen experts' bytes with the
            rows in and out and the routed pairs' operations
            (`pattern_moe_lm`);
  "state"   weights once and the stepped rows' states read and written
            (`retention_lm`).

None where there is no trace, the configuration is of another family or
the spans lack the arguments (a program from before they carried them).
Spans that carry the work and no run of the program, or for "gmm" no
operation of that name, raise: the names are part of the yardstick."""
import re

from benchmark.lib import counts, counts_pattern, counts_retention
from benchmark.readers import module_time
from benchmark.reduce import program_spans

LAUNCH, OBSERVE = "hvd.serve.launch", "hvd.serve.observe"


def traced_steps(spans, lo, hi):
    """device step -> its arguments: of the `launch` inside [lo, hi]
    that dispatched it and of the `observe` after its sync (whose `rows`
    is its own iteration's: the launch's stands)."""
    steps = {s.stats["dstep"]: dict(s.stats)
             for s in program_spans.named(spans, LAUNCH, lo, hi)
             if "dstep" in s.stats}
    for s in spans:
        d = s.stats.get("dstep")
        if s.name == OBSERVE and d in steps:
            steps[d] = {**s.stats, **steps[d]}
    return steps


def mean_work(steps, args):
    """The mean of each argument over the steps that carry all of
    `args`; None where none does."""
    whole = [st for st in steps.values() if all(a in st for a in args)]
    if not whole:
        return None
    return {a: sum(float(st[a]) for st in whole) / len(whole) for a in args}


def op_time_in_runs(t, match, pattern):
    """Device seconds of the operations whose name matches `pattern`
    that started inside a run, within [lo, hi], of the program of prefix
    `match` run most often there: the yardstick's one interval walk,
    which needs the runs' intervals where `module_time.picked_runs`
    gives lengths."""
    runs = {}
    for name, s, e in t.chips[0].modules:
        if name.startswith(match) and s >= t.lo and e <= t.hi:
            runs.setdefault(name, []).append((s, e))
    pat = re.compile(pattern)
    calls = sorted((s, e) for name, s, e in t.chips[0].ops
                   if pat.fullmatch(name))
    busy, i = 0.0, 0
    for lo, hi in sorted(max(runs.values(), key=len)):
        while i < len(calls) and calls[i][0] < lo:
            i += 1
        while i < len(calls) and calls[i][0] < hi:
            busy += calls[i][1] - calls[i][0]
            i += 1
    return busy


def _decode(ctx, w):
    return counts.decode_step_bytes(ctx.config, w["live_tokens"]) \
        / ctx.peaks["hbm_bytes_per_s"]


def _moe(ctx, w):
    return counts_pattern.decode_step_bytes(
        ctx.config, w["experts_hit"], w["live_tokens"], w["ring_tokens"],
        ctx.config["serve"]["weights_dtype"]) / ctx.peaks["hbm_bytes_per_s"]


def _gmm(ctx, w):
    m = ctx.config
    pairs = w["rows"] * m["num_experts_per_tok"]
    return max(
        counts_pattern.grouped_product_bytes(
            m, w["experts_hit"], pairs, m["serve"]["weights_dtype"])
        / ctx.peaks["hbm_bytes_per_s"],
        counts_pattern.sparse_layers(m) * counts_pattern.expert_flops(
            m, pairs) / ctx.peaks["bf16_flops_per_s"])


def _state(ctx, w):
    sv = ctx.config["serve"]
    return counts_retention.decode_step_bytes(
        ctx.config, w["rows"], sv["weights_dtype"], sv["state_dtype"]) \
        / ctx.peaks["hbm_bytes_per_s"]


#: kind -> the family it counts, the arguments it needs, the least
#: seconds a step of that mean work could take
KINDS = {
    "decode": ("decoder_lm", ("live_tokens",), _decode),
    "moe": ("pattern_moe_lm", ("experts_hit", "live_tokens", "ring_tokens"),
            _moe),
    "gmm": ("pattern_moe_lm", ("rows", "experts_hit"), _gmm),
    "state": ("retention_lm", ("rows",), _state),
}


def read(ctx, match: str, kind: str, op: str = None):
    family, args, least_s = KINDS[kind]
    t = ctx.trace
    if t is None or ctx.config.get("family") != family:
        return None
    work = mean_work(traced_steps(
        program_spans.of_cell(ctx.cell["name"]), t.lo, t.hi), args)
    if work is None:
        return None
    runs = module_time.picked_runs(ctx, match, "most_run")
    if not runs:
        raise RuntimeError(
            f"the traced window's spans carry {sorted(work)} and the "
            f"trace holds no run of a program named {match!r}*")
    if kind == "gmm":
        busy = op_time_in_runs(t, match, op)
        if not busy:
            raise RuntimeError(
                f"the traced steps hit experts and the decode program "
                f"ran no operation named {op!r}")
    else:
        busy = sum(runs)
    return 100.0 * least_s(ctx, work) / (busy / len(runs))
