"""What a served step costs the HOST with the profiler off, one form of
`InferenceServer._plain_step` against another, on the chip (PR 40; the
method of PR 33's probe, PERF.md 5): a server at `mistral7b_chat_steady`'s
shapes, 9 rows decoding, nothing admitted, no profiler session, the host's
clock around every `server.step()`, steps that alternate between the
forms on ONE server.

    python3 benchmark/records/probe_step_host.py <out.jsonl> <name>=<server.py> ...

Each `<server.py>` is `horovod_tpu/serve/server.py` of a commit to
compare with (unpacked by `git archive`); its `_plain_step` is compiled
into this checkout's server module and takes its turn beside this
checkout's own form, `here`: a round is one step of each form, the order
rotating from round to round, so that the rows' growing depth (a step
reads another block of 512 slots a row every 512 steps) falls on all
alike.  With no profiler session and no timeline a span is an annotation
that nobody records, so the difference is what the spans and their
arguments cost to build.  One line with each form's median step and its
paired differences from `here` over the rounds (median, quartiles, the
mean of the inner 80% and its standard error, us), and one with what a
`span` costs this host alone, bare and with five arguments.  The same
file given twice is the probe's own noise.  (Blocks of 60 to 100 steps a
form, the first design, did not resolve 50 us: PERF.md 6, PR 40.)"""
import ast
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ROWS, PROMPT, ROUNDS, WARM = 9, 512, 900, 40


def other_plain_step(path, server_mod):
    """`InferenceServer._plain_step` as `path` has it, compiled among
    this checkout's server module's names."""
    with open(path) as f:
        tree = ast.parse(f.read())
    cls = next(n for n in tree.body if isinstance(n, ast.ClassDef)
               and n.name == "InferenceServer")
    fn = next(n for n in cls.body if isinstance(n, ast.FunctionDef)
              and n.name == "_plain_step")
    names = dict(vars(server_mod))
    exec(compile(ast.Module([fn], []), path, "exec"), names)
    return names["_plain_step"]


def build_server():
    """The `InferenceServer` of `mistral7b_chat_steady` as its runner
    builds it, none of its warm rounds run; -> (server, vocabulary)."""
    import jax

    from benchmark.lib import harness
    from benchmark.runners import lm_serve

    class Unramped(lm_serve.Runner):
        def _ramp(self):
            pass

    harness.use_compile_cache(ROOT)
    _, cell, config, traffic, _ = harness.find_cell(
        ROOT, "mistral7b_chat_steady")
    ctx = harness.Context(root=ROOT, cell=cell, config=config,
                          traffic=traffic, seed=4000000007,
                          devices=jax.devices()[:1], peaks={})
    return Unramped(ctx).server, config["vocab_size"]


def probe(srv, vocab, others, out=None, rows=ROWS, prompt=PROMPT,
          rounds=ROUNDS, warm=WARM):
    """`others`: name -> path of a server.py.  `rounds` rounds of one
    step a form; a round's steps are paired."""
    import numpy as np

    from horovod_tpu.serve import InferenceServer
    from horovod_tpu.serve import server as server_mod

    forms = {"here": InferenceServer._plain_step,
             **{name: other_plain_step(path, server_mod)
                for name, path in others.items()}}
    names = list(forms)
    # what another form may ask of a cache that this checkout's lacks
    for name in ("view_read_pct", "state_read_pct"):
        if not hasattr(srv.pool, name):
            setattr(srv.pool, name, lambda positions: None)
    rng = np.random.default_rng(7)
    budget = srv.max_seq_tokens - prompt
    if rows + len(names) * (warm + rounds) >= budget:
        raise ValueError(f"{len(names)} forms x {warm + rounds} steps "
                         f"outlast the rows' {budget} tokens")
    for _ in range(rows):           # one a step: the one-row gather only
        srv.submit(rng.integers(0, vocab, prompt), budget)
        srv.step()
    if len(srv.sched.active) != rows or srv.sched.queue_depth():
        raise RuntimeError("not every row boarded")

    taken = {name: [] for name in names}
    try:
        for form in forms.values():
            InferenceServer._plain_step = form
            for _ in range(warm):
                srv.step()
        for i in range(rounds):     # a step a form, the order rotating
            k = i % len(names)
            for name in names[k:] + names[:k]:
                InferenceServer._plain_step = forms[name]
                t = time.perf_counter()
                srv.step()
                taken[name].append(1e3 * (time.perf_counter() - t))
    finally:
        InferenceServer._plain_step = forms["here"]
    summary = {"rows": rows, "rounds": rounds,
               "median_ms": {n: statistics.median(taken[n]) for n in names}}
    for name in names[1:]:
        diffs_us = [1e3 * (a - b)
                    for a, b in zip(taken["here"], taken[name])]
        q = statistics.quantiles(diffs_us, n=4)
        inner = sorted(diffs_us)[len(diffs_us) // 10:
                                 len(diffs_us) - len(diffs_us) // 10]
        summary[f"here_less_{name}_us"] = {
            "median": statistics.median(diffs_us), "q1": q[0], "q3": q[2],
            "mean_of_inner_80pct": statistics.fmean(inner),
            "standard_error": statistics.stdev(inner) / len(inner) ** 0.5}
    line = json.dumps(summary)
    print(line, flush=True)
    if out:
        out.write(line + "\n")


def span_cost_us(n=200000):
    """One `with span(...)` with no session and no timeline, us."""
    from horovod_tpu.utils.timeline import span
    args = {"dstep": 1, "rows": 9, "rows_pct": 28.12, "live_tokens": 5000,
            "view_read_pct": 7.0}
    out = {}
    for name, a in (("bare", None), ("five_arguments", args)):
        t = time.perf_counter()
        for _ in range(n):
            with span("launch", "serve", a):
                pass
        out[name] = 1e6 * (time.perf_counter() - t) / n
    return out


def main(argv) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    srv, vocab = build_server()
    with open(argv[0], "a") as out:
        probe(srv, vocab, dict(a.split("=", 1) for a in argv[1:]), out)
        line = json.dumps({"span_cost_us": span_cost_us()})
        print(line)
        out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
