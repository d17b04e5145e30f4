"""CPU rehearsal of the latent cell at a tiny preset, beside
test_rehearsal_pattern.py: the last line's keys, the new counters, that
the float8 control comes out as not correct, that each term of the layer
left out of the PROGRAM makes `correct` false, and the new reader on
hand-made traces.  A CPU run gives counts and correctness, never a time.

tiny.py knows the families it was written with, so this file cuts the
new family itself, in the same temporary root and as new files only."""
import io
import json
import os
import time

import pytest

from benchmark.lib import harness
from benchmark.readers import ReadContext, traced_latent
from benchmark.reduce import program_spans, xplane
from benchmark.reduce.program_spans import Span
from benchmark.tests import tiny
from benchmark.tests.test_rehearsal import build

CELL = "gigachat702b_longctx_steady"
CONFIG = "gigachat3.1-702b-a36b-serve.json"

# Every mechanism at a size a test can hold (tests/test_latent.py's): 4
# heads of 16 + 8 and 24 over a latent of 32, YaRN over 16 positions with
# m^2 = 1.46 on the scale, 32 experts in 4 groups of which 2 are kept, 4 a
# token, the first 16 held (half of them, so that the routed part weighs
# enough for the routed scale to show) and a seeded bias of std 0.1 (so
# that a choice made without it shows).
TINY_LATENT = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "n_routed_experts": 16, "router_width": 32,
    "experts_held": [0, 16], "kv_lora_rank": 32, "q_lora_rank": 48,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "qk_nope_head_dim": 16,
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "rope_type": "yarn"}}

# Read on the CPU at these sizes (bf16 program, fp8 control; seeds 5, 7,
# 3000000019; 58 to 68 positions): the program's mean gap 0.014 to 0.025,
# the control's 0.21 to 0.24, a term left out 0.21 (the routed scale) to
# 1.30 (the shared key's part of the score).  With 8 experts held and a
# bias of std 0.02 the routed scale and the bias in the choice read 0.05
# to 0.09: too near any limit.
TINY_MEAN_GAP = 0.08


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_latent")))
    src = os.path.join(tiny.ROOT, "benchmark")
    cfg = harness.load_json(os.path.join(src, "configs", CONFIG))
    cfg.update(TINY_LATENT)
    cfg["assumed"] = dict(cfg["assumed"], router_bias_std=0.1)
    cfg["serve"].update(check_requests=8, page_tokens=4)
    cfg["limits"] = {"mean_logit_gap": TINY_MEAN_GAP}
    with open(os.path.join(root, "bench", "configs", CONFIG), "w") as f:
        json.dump(cfg, f)
    tr = harness.load_json(
        os.path.join(src, "traffic", "longctx_steady.json"))
    tr["pairs"] = tr["pairs"][::3]        # 4 pairs: 14..26 in, 5..11 out
    for p in tr["pairs"]:
        p["prompt"] = p["prompt"] // 1024 + 10
        p["output"] = p["output"] // 128 + 3
    tr["ramp"]["requests"] = 4
    tr["ramp"].update(warm_pair={"prompt": 2, "output": 2}, max_group=2,
                      settle_steps=4, stagger_steps=2)
    tr["server"].update(max_batch=3, max_seq_tokens=48, pool_pages=36)
    tr["arrivals"].update(rate_per_s=20.0, horizon_s=8.0)
    with open(os.path.join(root, "bench", "traffic",
                           "longctx_steady.json"), "w") as f:
        json.dump(tr, f)
    return root


def run(root, seed, seconds=1.0):
    out = io.StringIO()
    rc = harness.run_cell(root, CELL, seed, seconds, False,
                          time.perf_counter(), require_chip=False,
                          peaks=tiny.PEAKS, out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


def test_last_line(root, capsys):
    rc, lines, last = run(root, 3000000019)
    assert rc == 0
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0, lines
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert any(l.startswith("check mean gap") and "limit" in l
               for l in lines)
    assert "window: held experts hit a sparse layer and step" in \
        capsys.readouterr().out


def test_counters_and_the_latent_cache(root):
    r = build(root, CELL, 11)
    res = r.window(0.5)
    c, srv = res.counters, r.server
    assert c["moe_layer_steps"] == 3 * c["device_steps"] > 0
    rows = c["occupancy_sum"] * srv.max_batch
    assert c["pairs_sum"] == pytest.approx(rows * 4 * 3)
    # 16 of 32 experts are held: a share of the pairs lies here
    assert 0 < c["pairs_here_sum"] < c["pairs_sum"]
    assert c["experts_hit_sum"] <= 16 * c["moe_layer_steps"]
    assert c["experts_hit_sum"] <= c["pairs_here_sum"]
    from horovod_tpu.serve.pool import KindKVPool
    assert isinstance(srv.pool, KindKVPool) and srv.pool.latent
    assert srv.pool.k.shape[2:] == (1, 4, 32)
    done = [t for t in r.finished if t.plan.index >= 0 and not t.failed]
    assert done and all(len(t.seq.generated) == t.plan.output_len
                        for t in done)


def test_lower_precision_control_is_not_correct(root):
    r = build(root, CELL, 5)
    r.window(0.5)
    got = r.readings("fp8")
    assert all(c.ok for c in got["program"]), got["program"]
    assert not got["control"][0].ok, got["control"]
    assert got["control"][0].what == got["program"][-1].what


def _broken(monkeypatch, what):
    """Replace one piece of the program by one that leaves a term out."""
    import jax.numpy as jnp

    from horovod_tpu.models import decode, experts, transformer

    if what == "m^2 dropped from the softmax scale":
        monkeypatch.setattr(
            transformer.LatentSpec, "softmax_scale", property(
                lambda sp: (sp.nope_dim + sp.rope_dim) ** -0.5))
    elif what == "the shared key's part of the score left out":
        real = decode._latent_queries
        monkeypatch.setattr(
            decode, "_latent_queries", lambda *a, **kw: (
                lambda qn, qr: (qn, jnp.zeros_like(qr)))(*real(*a, **kw)))
    elif what == "the latent's norm left out":
        real = decode._rmsnorm
        rank = TINY_LATENT["kv_lora_rank"]
        monkeypatch.setattr(
            decode, "_rmsnorm", lambda scale, x: x
            if scale.shape[-1] == rank and x.shape[-1] == rank
            else real(scale, x))
    elif what == "the group limit left out":
        monkeypatch.setattr(experts, "_kept_groups",
                            lambda choice, cfg: choice)
    elif what == "routed scale dropped":
        real = experts.route
        monkeypatch.setattr(
            experts, "route", lambda router, h, cfg, bias=None: (
                lambda idx, w: (idx, w / cfg.routed_scale))(
                    *real(router, h, cfg, bias)))
    elif what == "shared expert left out":
        real = experts._expert_pass
        monkeypatch.setattr(
            experts, "_expert_pass", lambda mp, *a, **kw: real(
                {n: p for n, p in mp.items() if n != "shared"}, *a, **kw))
    else:
        assert what == "the bias left out of the choice"
        real = experts.route
        monkeypatch.setattr(
            experts, "route",
            lambda router, h, cfg, bias=None: real(router, h, cfg, None))


@pytest.mark.parametrize("what", [
    "m^2 dropped from the softmax scale",
    "the shared key's part of the score left out",
    "the latent's norm left out", "the group limit left out",
    "routed scale dropped", "shared expert left out",
    "the bias left out of the choice"])
def test_term_left_out(root, monkeypatch, what):
    from horovod_tpu.models import decode
    from horovod_tpu.serve import server

    decode._spec_step_fn.cache_clear()       # programs are kept by config
    server._prefill_fn.cache_clear()
    _broken(monkeypatch, what)
    try:
        rc, lines, last = run(root, 7)
    finally:
        decode._spec_step_fn.cache_clear()
        server._prefill_fn.cache_clear()
    assert rc == 0 and last["correct"] is False, (what, lines[-12:])
    assert any("mean gap" in l and "NOT CORRECT" in l for l in lines), \
        (what, lines[-12:])


# -- the traced reader, on hand-made traces ---------------------------------

STEP, PREFILL = "jit__lambda(111)", "jit__lambda(222)"
LO, HI, PERIOD, SHARE = 35.0, 40.0, 0.05, 80.0
OPS = {"step": None, "attn": r"decode_attention(\.\d+)?",
       "gmm": r"gmm(\.\d+)?"}
WORK = dict(rows=10, live_tokens=90000, experts_hit=20, pairs_here=18)


def _case(kind, with_args=True, with_runs=True, with_ops=True):
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda *p: harness.load_json(os.path.join(bench, *p))
    ctx = ReadContext(
        cell={"name": "a_cell"}, config=load("configs", CONFIG),
        traffic=load("traffic", "longctx_steady.json"),
        peaks=load("peaks.json")["TPU v5 lite"], chips=1, samples={},
        trace=None, memory_peak_bytes=0, counters={})
    busy = traced_latent.KINDS[kind][1](ctx, WORK) / (SHARE / 100)
    name = {"attn": "decode_attention", "gmm": "gmm"}.get(kind)
    spans, modules, ops = [], [], []
    for i in range(int((HI - LO) / PERIOD)):
        at, dstep = LO + i * PERIOD, 7000 + i
        launch = dict(view_read_pct=30.0)
        if with_args:
            launch.update(dstep=dstep, rows=WORK["rows"], rows_pct=41.67,
                          live_tokens=WORK["live_tokens"])
        spans.append(Span("hvd.serve.launch", at, at + 0.002, launch))
        observe = dict(step=i, rows=WORK["rows"], admitted=0, finished=0,
                       decided=1)
        if with_args:
            observe.update(dstep=dstep, experts_hit=WORK["experts_hit"],
                           pairs_here=WORK["pairs_here"])
        spans.append(Span("hvd.serve.observe", at + PERIOD - 0.002,
                          at + PERIOD - 0.001, observe))
        if name:                 # the kernel's calls inside a longer run
            run = (at + 0.002, at + 0.002 + 2 * busy)
            if with_ops:
                ops += [(f"{name}.{j}" if j else name,
                         run[0] + j * busy / 5, run[0] + (j + 1) * busy / 5)
                        for j in range(5)]
            ops.append(("fusion.9", run[0] + busy, run[1]))
        else:
            run = (at + 0.002, at + 0.002 + busy)
            ops.append(("fusion.9", *run))
        assert run[1] < at + PERIOD - 0.002, "the step does not fit"
        if with_runs:
            modules.append((STEP, *run))
    if with_runs:                # a prefill program, run less often
        modules += [(PREFILL, LO + 0.04 + i, LO + 0.045 + i)
                    for i in range(4)]
    ctx.trace = xplane.Reduced([xplane.ChipTrace(ops, modules)], [], LO, HI)
    return ctx, spans


def _read(monkeypatch, kind, ctx, spans):
    monkeypatch.setattr(program_spans, "of_cell", lambda cell: tuple(spans))
    return traced_latent.read(ctx, "jit__lambda(", kind, op=OPS[kind])


@pytest.mark.parametrize("kind", sorted(OPS))
def test_traced_work_over_traced_time(monkeypatch, kind):
    ctx, spans = _case(kind)
    assert _read(monkeypatch, kind, ctx, spans) == pytest.approx(SHARE)


@pytest.mark.parametrize("kind", sorted(OPS))
def test_nothing_to_read_reads_none(monkeypatch, kind):
    """Spans without the arguments (the parent of the PR that brought
    `pairs_here`), another family's configuration, no trace."""
    ctx, spans = _case(kind, with_args=False)
    assert _read(monkeypatch, kind, ctx, spans) is None
    ctx, spans = _case(kind)
    ctx.config = dict(ctx.config, family="pattern_moe_lm")
    assert _read(monkeypatch, kind, ctx, spans) is None
    ctx, spans = _case(kind)
    ctx.trace = None
    assert _read(monkeypatch, kind, ctx, spans) is None


@pytest.mark.parametrize("kind", sorted(OPS))
def test_work_without_a_matching_run_raises(monkeypatch, kind):
    ctx, spans = _case(kind, with_runs=False)
    with pytest.raises(RuntimeError, match="no run of a program"):
        _read(monkeypatch, kind, ctx, spans)


@pytest.mark.parametrize("kind", ["attn", "gmm"])
def test_work_and_no_kernel_of_that_name_raises(monkeypatch, kind):
    ctx, spans = _case(kind, with_ops=False)
    with pytest.raises(RuntimeError, match="no operation named"):
        _read(monkeypatch, kind, ctx, spans)
