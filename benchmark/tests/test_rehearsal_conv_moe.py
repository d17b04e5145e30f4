"""CPU rehearsal of the convolution-and-experts training cell at a tiny
preset, beside test_rehearsal.py: the last line's keys, the routing
counters, that the float8 control comes out as not correct, and that each
term of the layer left out of (or bent in) the reference's place makes
`correct` false.  A CPU run gives counts and correctness, never a time.

tiny.py knows the families it was written with, so this file cuts the new
family itself, in the same temporary root and as new files only."""
import io
import json
import os
import time

import pytest

from benchmark.lib import harness, traincheck
from benchmark.tests import tiny
from benchmark.tests.test_rehearsal import build

CELL = "lfm2_8b_train_8k"

# Every mechanism at a size a test can hold: a convolution layer with the
# dense MLP, then an attention layer (q/k norm, 128 tokens: the flash
# kernel) and a convolution layer with 4 held of 16 routed experts, 4 a
# token, chosen with the bias.
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_experts": 4, "router_width": 16, "experts_held": [0, 4]}

# The bias at ten times the configuration's 0.02, so that at this size
# (where bf16 alone moves a leaf's norm by 5e-3) the bias put into the
# weights shows.
TINY_BIAS_STD = 0.2

# Read on the CPU at these sizes (bf16 program, fp8 control; seeds 7 and
# 3000000019).  Losses: program <= 3.6e-4.  First gradient's norm, worst
# leaf: program 2.7e-3 to 4.9e-3; fp8 3.6e-2 to 6.3e-2; a dropped routed
# scale 0.38; the bias in the weights 3.4e-2 to 6.2e-2; a non-causal tap
# 0.21 to 0.26; an expert left out 6e-2 to 0.17.  Parameters' change:
# program 4.4e-3 to 7.5e-3; fp8 1.4e-2 to 3.4e-2.
TINY_LIMITS = {"loss_rel": 2.5e-3, "grad_norm_rel": 0.015,
               "delta_norm_rel": 0.012}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("tiny_conv_moe")))
    src = os.path.join(tiny.ROOT, "benchmark")
    cfg = harness.load_json(
        os.path.join(src, "configs", "lfm2-8b-a1b-train.json"))
    cfg.update(TINY)
    cfg["limits"] = TINY_LIMITS
    cfg["assumed"]["router_bias_std"] = TINY_BIAS_STD
    with open(os.path.join(root, "bench", "configs",
                           "lfm2-8b-a1b-train.json"), "w") as f:
        json.dump(cfg, f)
    tr = harness.load_json(os.path.join(src, "traffic", "seq8k_b4.json"))
    tr.update(seq_len=128, per_chip_batch=2, resident_batches=2)
    with open(os.path.join(root, "bench", "traffic", "seq8k_b4.json"),
              "w") as f:
        json.dump(tr, f)
    return root


def run(root, seed, seconds=1.0):
    out = io.StringIO()
    rc = harness.run_cell(root, CELL, seed, seconds, False,
                          time.perf_counter(), require_chip=False,
                          peaks=tiny.PEAKS, out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


def test_last_line(root):
    rc, lines, last = run(root, 3000000019)
    assert rc == 0
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0, lines
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_mfu", "setup_s"}
    assert sum(l.startswith("check ") for l in lines) == 5


def test_counters_and_per_layer_metrics(root):
    """The routing counts of the window, and the two metrics that read
    them (the device-trace ones need a trace: None without)."""
    from benchmark.readers import ReadContext, ratio, train_gmm
    r = build(root, CELL, 11)
    res = r.window(0.5)
    c = res.counters
    sparse, k = 2, r.m["num_experts_per_tok"]
    assert c["moe_layer_steps"] == sparse * res.attempted > 0
    assert c["pairs_sum"] == c["moe_layer_steps"] * r.B * r.T * k
    assert 0 < c["pairs_here_sum"] < c["pairs_sum"]
    assert c["moe_layer_steps"] <= c["experts_hit_sum"] \
        <= 4 * c["moe_layer_steps"]
    assert c["pairs_here_sum"] / 4 <= c["expert_load_max_sum"] \
        <= c["pairs_here_sum"]
    manifest = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    names = {m["name"] for m in harness.cell_metrics(manifest, cell,
                                                     "per_layer")}
    assert names == {
        "train_step_ms.mfu", "device_idle_share.mfu", "peak_hbm_gib.mfu",
        "window_compiles.mfu", "expert_gmm_roofline.mfu",
        "expert_kernel_share.mfu", "expert_pairs_here_share.mfu",
        "expert_load_max_share.mfu"}
    rctx = ReadContext(cell=cell, config=r.m, traffic=r.ctx.traffic,
                       peaks=tiny.PEAKS, chips=1, counters=c,
                       samples=res.samples, trace=None,
                       memory_peak_bytes=0)
    spec = lambda n: harness.load_json(os.path.join(
        root, "bench", "metrics", n + ".json"))["params"]
    share = ratio.read(rctx, **spec("expert_pairs_here_share.mfu"))
    assert 10 < share < 45                      # 25 when balanced
    # held = 4 here, the metric's scale is the cell's 8 held
    load = ratio.read(rctx, **spec("expert_load_max_share.mfu")) / 2
    assert 100 <= load <= 400
    assert train_gmm.read(rctx, **spec("expert_gmm_roofline.mfu")) is None
    # a program without the counter (the parent's): nothing, no raise
    rctx.counters = {}
    assert ratio.read(rctx, **spec("expert_pairs_here_share.mfu")) is None
    assert train_gmm.read(rctx, **spec("expert_kernel_share.mfu")) is None


BROKEN = {
    "a dropped routed scale": dict(routed_scale=0.5),
    "the bias put into the weights": dict(bias_in_weights=True),
    "a non-causal tap": dict(causal_taps=False),
    "an expert left out": dict(skip_expert=1),
}


@pytest.fixture(scope="module")
def built(root):
    r = build(root, CELL, 7)
    program = r.program
    r.free_program()
    return r, program, r.reference()


@pytest.mark.parametrize("what", ["fp8"] + sorted(BROKEN))
def test_controls(built, what):
    """The program is inside every limit; the float8 control, and the
    reference bent in one term put in the program's place, are outside at
    least one (`TINY_LIMITS` has the readings)."""
    r, program, want = built
    limits = r.m["limits"]
    assert all(c.ok for c in traincheck.compare(program, want, limits))
    got = r.reference("fp8") if what == "fp8" else r.reference(
        **BROKEN[what])
    checks = traincheck.compare(got, want, limits)
    assert not all(c.ok for c in checks), [(c.what, c.value) for c in checks]
