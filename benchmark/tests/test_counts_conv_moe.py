"""`lib/counts_conv_moe.py` against hand sums at LFM2-8B-A1B's widths as
`configs/lfm2-8b-a1b-train.json` cuts them, and `readers/train_gmm.py`
on a made-up trace."""
import json
import os

import pytest

from benchmark.lib import counts_conv_moe as C
from benchmark.lib import weights_conv_moe as W
from benchmark.readers import ReadContext, train_gmm
from benchmark.reduce import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(HERE, "..", "configs",
                           "lfm2-8b-a1b-train.json")) as f:
        return json.load(f)


def test_the_layers_run_are_source_layers_1_to_5(m):
    assert W.kinds(m) == [
        ("conv", "dense"), ("full_attention", "experts"),
        ("conv", "experts"), ("conv", "experts"), ("conv", "experts")]
    assert W.held(m) == (0, 8) and W.router_width(m) == 32


def test_parameters_by_hand(m):
    D = 2048
    conv = D * 6144 + D * D + D * 3                       # 16.78 M
    attn = 2 * D * 2048 + 2 * D * 512 + 2 * 64            # 10.49 M
    dense = 3 * D * 7168                                  # 44.04 M
    expert = 3 * D * 1792                                 # 11.01 M
    router = D * 32 + 32 + 2 * D                          # with both norms
    assert C.conv_params(m) == conv == 16_783_360
    assert C.attention_params(m) == attn == 10_485_888
    assert C.expert_params(m) == expert == 11_010_048
    assert C.layer_params(m, ("conv", "dense")) == conv + dense + 2 * D
    assert C.layer_params(m, ("full_attention", "experts")) \
        == attn + 8 * expert + router
    total = (16384 * D + D + (conv + dense + 2 * D)
             + (attn + 8 * expert + router)
             + 3 * (conv + 8 * expert + router))
    assert C.param_count(m) == total == 507_820_288       # the 507.8 M
    # 16 bytes a parameter: 8.13 GB; the routed experts 69% of them
    assert round(total * 16 / 1e9, 2) == 8.13
    assert round(4 * 8 * expert / total, 2) == 0.69


def test_operations_a_token_by_hand(m):
    D, T = 2048, 8192
    conv = 2 * D * 6144 + 2 * D * D                       # 33.55 M
    proj = 2 * D * (2048 + 2 * 512) + 2 * 2048 * D        # 20.97 M
    keys = 4 * 32 * 64 * (T + 1) / 2                      # 33.56 M
    dense = 6 * D * 7168                                  # 88.08 M
    routed = 2 * D * 32 + (4 * 8 / 32) * 6 * D * 1792     # 22.15 M
    head = 2 * D * 16384                                  # 67.11 M
    assert C.mixer_flops_per_token(m, "conv", T) == conv
    assert C.mixer_flops_per_token(m, "full_attention", T) == proj + keys
    assert C.ffn_flops_per_token(m, "dense") == dense
    assert C.ffn_flops_per_token(m, "experts") == routed
    fwd = (conv + dense) + (proj + keys + routed) + 3 * (conv + routed) \
        + head
    assert C.forward_flops_per_token(m, T) == fwd
    assert round(fwd / 1e6, 1) == 432.5                   # the 432 M
    assert round((conv + dense) / 1e6, 1) == 121.6
    assert round((proj + keys + routed) / 1e6, 1) == 76.7
    assert round((conv + routed) / 1e6, 1) == 55.7
    step = C.train_flops_per_sample(m, {"seq_len": T}) * 4
    assert step == 3 * fwd * T * 4
    assert round(step / 1e12, 1) == 42.5                  # TFLOP a step
    # the experts' products: 20% of the counted operations
    assert round(4 * (routed - 2 * D * 32) / fwd, 2) == 0.20


def test_grouped_products_by_hand(m):
    pairs = 4 * 32768                   # a balanced step, 4 sparse layers
    flops = C.grouped_flops(m, pairs)
    assert flops == 9 * 2 * pairs * 2048 * 1792
    moved = C.grouped_bytes(m, pairs)
    experts = 4 * 8 * 3 * 2048 * 1792 * 2
    rows = pairs * 3 * (2048 + 1792) * 2
    assert moved == 3 * (experts + rows)
    # compute-bound on the v5e: 44.0 ms of operations, 13.6 ms of bytes
    assert round(1e3 * flops / 197e12, 1) == 44.0
    assert round(1e3 * moved / 819e9, 1) == 13.6


def _ctx(m, ops, counters):
    step = ("jit_train_step(123)", 0.0, 1.0)
    chip = xplane.ChipTrace(ops, [step, ("jit_train_step(123)", 1.0, 2.0),
                                  ("jit__gap(9)", 2.0, 2.1)])
    return ReadContext(cell={}, config=m, traffic={}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1,
        counters=counters, samples={},
        trace=xplane.Reduced([chip], [], 0.0, 3.0), memory_peak_bytes=0)


def test_train_gmm_reader(m):
    params = dict(match="jit_train_step", op="t?gmm(\\.\\d+)?")
    counters = {"moe_layer_steps": 8.0, "pairs_here_sum": 8 * 32768.0}
    ops = [("gmm.3", 0.1, 0.15), ("tgmm", 0.5, 0.53), ("fusion.1", 0.6, 0.9),
           ("gmm", 1.2, 1.25), ("tgmm.12", 1.5, 1.53),
           ("gmm.3", 2.02, 2.05)]          # outside the step's program
    ctx = _ctx(m, ops, counters)
    share = train_gmm.read(ctx, stat="share_of_step", **params)
    assert share == pytest.approx(100 * 0.16 / 2.0)
    roof = train_gmm.read(ctx, stat="roofline", **params)
    least = C.grouped_flops(m, 4 * 32768) / 197e12
    assert roof == pytest.approx(100 * least / 0.08)
    # no counter (a program without it): nothing to read, no error
    assert train_gmm.read(_ctx(m, ops, {}), stat="roofline",
                          **params) is None
    # pairs counted and no such operation in the step: an error
    with pytest.raises(RuntimeError, match="no operation"):
        train_gmm.read(_ctx(m, [("fusion.1", 0.1, 0.2)], counters),
                       stat="roofline", **params)
