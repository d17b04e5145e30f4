"""CPU rehearsal of the patterned cell at a tiny preset, beside
test_rehearsal.py: the last line's keys, the new counters, that the
float8 control comes out as not correct, and that each term of the layer
left out of the program underneath makes `correct` false.  A CPU run
gives counts and correctness, never a time.

tiny.py knows the families it was written with, so this file cuts the
new family itself, in the same temporary root and as new files only."""
import glob
import io
import json
import os
import time

import jax
import pytest

from benchmark.lib import harness
from benchmark.reduce import program_spans
from benchmark.tests import tiny
from benchmark.tests.test_rehearsal import build

CELL = "laguna_xs2_codegen_steady"

# Every mechanism at a size a test can hold: two kinds of attention layer
# that differ in heads, a window of 8 that every prompt passes, YaRN over
# an original context of 16 on half of each head, 16 experts of which 4 a
# token.
TINY_PATTERN = {
    "hidden_size": 64, "intermediate_size": 128,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "num_hidden_layers": 5, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "sliding_window": 8,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}}

# Read on the CPU at these sizes (bf16 program, fp8 control; seeds 5, 7,
# 11, 3000000019; 46 to 60 positions): the program's mean gap 0.008 to
# 0.078 (its served token the reference's first at 85 to 96% of
# positions, its widest gap 0.36 to 1.94: one flipped expert), the
# control's 0.49 to 0.67.
TINY_MEAN_GAP = 0.2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_pattern")))
    src = os.path.join(tiny.ROOT, "benchmark")
    cfg = harness.load_json(
        os.path.join(src, "configs", "laguna-xs2-serve.json"))
    cfg.update(TINY_PATTERN)
    cfg["serve"].update(check_requests=8, page_tokens=4)
    cfg["limits"] = {"mean_logit_gap": TINY_MEAN_GAP}
    with open(os.path.join(root, "bench", "configs",
                           "laguna-xs2-serve.json"), "w") as f:
        json.dump(cfg, f)
    tr = harness.load_json(
        os.path.join(src, "traffic", "codegen_steady.json"))
    tr["pairs"] = tr["pairs"][::3]        # 4 pairs: 12..26 in, 4..11 out
    for p in tr["pairs"]:
        p["prompt"] = p["prompt"] // 256 + 10
        p["output"] = p["output"] // 128 + 3
    tr["ramp"]["requests"] = 4
    tr["ramp"].update(warm_pair={"prompt": 2, "output": 2}, max_group=2,
                      settle_steps=4, stagger_steps=2)
    tr["server"].update(max_batch=3, max_seq_tokens=48, pool_pages=36)
    tr["arrivals"].update(rate_per_s=20.0, horizon_s=8.0)
    with open(os.path.join(root, "bench", "traffic",
                           "codegen_steady.json"), "w") as f:
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
    # the runner's own lines go where lm_serve's go
    assert "window: experts hit a sparse layer and step" in \
        capsys.readouterr().out


def _window_and_its_launches(r, tmp_path, seconds):
    """The window run inside a profiler session -> its result and the
    `hvd.serve.launch` spans of the window's own device steps, read as a
    traced run reads them (`reduce/program_spans.py`)."""
    steps0 = r.server.device_steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = r.window(seconds)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    mine = range(steps0, steps0 + res.counters["device_steps"])
    return res, [s for s in program_spans.read_file(path)
                 if s.name == "hvd.serve.launch"
                 and s.stats.get("dstep") in mine]


def test_counters_and_both_kinds_of_cache(root, tmp_path):
    r = build(root, CELL, 11)
    res, launches = _window_and_its_launches(r, tmp_path, 0.5)
    c, srv = res.counters, r.server
    sparse = r.m["mlp_layer_types"][:r.m["num_hidden_layers"]].count(
        "sparse")
    assert c["moe_layer_steps"] == sparse * c["device_steps"] > 0
    # a step's tokens choose at least experts_per_token experts a layer,
    # at most all of them; the fullest expert takes at most every row
    k, E = r.m["num_experts_per_tok"], r.m["num_experts"]
    assert k * c["moe_layer_steps"] <= c["experts_hit_sum"] \
        <= E * c["moe_layer_steps"]
    assert c["moe_layer_steps"] <= c["expert_load_max_sum"] \
        <= srv.max_batch * c["moe_layer_steps"]
    # every prompt is longer than the window: a ring holds `window`
    # tokens of each stepped row, the pages all of them (the step's own
    # spans say it: what the traced rooflines read)
    assert len(launches) == c["device_steps"]
    assert sum(s.stats["rows"] for s in launches) == pytest.approx(
        c["occupancy_sum"] * srv.max_batch)
    for s in launches:
        assert s.stats["ring_tokens"] == \
            r.m["sliding_window"] * s.stats["rows"]
        assert s.stats["live_tokens"] > s.stats["ring_tokens"]
    pool = srv.pool
    assert pool.ring_bytes > 0 and pool.page_bytes > 0
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
    from horovod_tpu.models import decode, experts, transformer

    if what == "routed scale dropped":
        real = experts.route
        monkeypatch.setattr(
            experts, "route", lambda router, h, cfg, *a: (
                lambda idx, w: (idx, w / cfg.routed_scale))(
                    *real(router, h, cfg, *a)))
    elif what == "shared expert left out":
        real = experts.expert_layer
        monkeypatch.setattr(
            experts, "expert_layer", lambda mp, *a, **kw: real(
                {n: p for n, p in mp.items() if n != "shared"}, *a, **kw))
    elif what == "gate left out":
        monkeypatch.setattr(decode, "_head_gate",
                            lambda lp, h, o, cfg: o)
    elif what == "window layer given the whole context":
        real = transformer._kind_cfg.__wrapped__

        def kind_cfg(cfg, kind):          # a ring that holds every token
            kc = real(cfg, kind)
            return transformer.dataclasses.replace(
                kc, attn_window=64) if kc.attn_window else kc

        monkeypatch.setattr(transformer, "_kind_cfg", kind_cfg)
    else:
        assert what == "full layer capped at the window"
        # the cache still holds every token; the layer looks at 8
        for name in ("_decode_layer", "_prefill_layer"):
            real = getattr(decode, name)

            def capped(*a, cfg, real=real, **kw):
                if cfg.prompt_attention == "flash" and not cfg.attn_window:
                    cfg = transformer.dataclasses.replace(cfg,
                                                          attn_window=8)
                return real(*a, cfg=cfg, **kw)

            monkeypatch.setattr(decode, name, capped)


@pytest.mark.parametrize("what", [
    "routed scale dropped", "shared expert left out", "gate left out",
    "window layer given the whole context",
    "full layer capped at the window"])
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
