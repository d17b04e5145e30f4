"""`lib/counts_pattern.py` against parameters, bytes and operations
counted by hand, at Laguna-XS.2's published widths cut to five layers."""
import json
import os

import pytest

from benchmark.lib import counts_pattern as cp

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "laguna-xs2-serve.json")) as f:
    M = json.load(f)

D, d, Hkv, V = 2048, 128, 8, 100352


def test_the_file_is_the_published_row():
    assert (M["hidden_size"], M["head_dim"], M["num_key_value_heads"],
            M["vocab_size"]) == (D, d, Hkv, V)
    assert M["num_hidden_layers"] == 5
    assert M["layer_types"][:5] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert M["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert M["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(M["layer_types"]) == 40       # the source's lists, whole


def test_attention_by_hand():
    # q and o: D x H x d each; k and v: D x 8 x d each; gate D x H; norm
    full = 2 * D * 48 * d + 2 * D * 8 * d + D * 48 + D
    sliding = 2 * D * 64 * d + 2 * D * 8 * d + D * 64 + D
    assert cp.attention_params(M, 48) == full == 29_460_480
    assert cp.attention_params(M, 64) == sliding == 37_881_856
    assert full == pytest.approx(29.4e6, rel=3e-3)
    assert sliding == pytest.approx(37.7e6, rel=6e-3)


def test_experts_and_the_rest_by_hand():
    assert cp.expert_params(M) == 3 * D * 512 == 3_145_728   # 6.29 MB bf16
    assert cp.expert_params(M) * 2 == pytest.approx(6.29e6, rel=1e-3)
    assert 256 * cp.expert_params(M) == pytest.approx(805.3e6, rel=1e-4)
    dense = D + 3 * D * 8192
    sparse = D + D * 256 + 3 * D * 512       # norm, router, shared expert
    assert cp.mlp_params_outside_experts(M, "dense") == dense
    assert cp.mlp_params_outside_experts(M, "sparse") == sparse
    assert dense == pytest.approx(50.3e6, rel=1e-3)
    assert cp.sparse_layers(M) == 4


def test_weights_are_7_33_gb():
    outside = (V * D + D                          # tied embedding, norm
               + 2 * 29_460_480 + 3 * 37_881_856  # 2 full, 3 sliding
               + (D + 3 * D * 8192)               # layer 0's MLP
               + 4 * (D + D * 256 + 3 * D * 512))
    assert cp.params_outside_experts(M) == outside
    total = outside + 4 * 256 * 3_145_728
    assert cp.param_count(M) == total
    assert total == pytest.approx(3.664e9, rel=1e-3)
    assert total * 2 == pytest.approx(7.33e9, rel=1e-3)      # bf16 bytes
    assert outside * 2 == pytest.approx(0.886e9, rel=5e-3)
    # a chip of 32 that share a layer's experts would hold 8 of them
    assert cp.param_count(M, 8) == outside + 4 * 8 * 3_145_728


def test_decode_step_bytes_by_hand():
    assert cp.cache_bytes_per_token_layer(M) == 2 * 8 * 128 * 2 == 4096
    w = cp.params_outside_experts(M) * 2
    assert cp.decode_step_bytes(M, 0, 0, 0) == w
    # 26 rows at 2700 tokens: 142 distinct experts in each of 4 layers,
    # 2 full layers of the whole depth, 3 rings of 512
    got = cp.decode_step_bytes(M, 4 * 142, 26 * 2700, 26 * 512)
    assert got == w + 568 * 6_291_456 + 2 * 70_200 * 4096 \
        + 3 * 13_312 * 4096
    assert got == pytest.approx(5.2e9, rel=0.02)
    # every expert read: 6.44 GB of experts
    assert cp.expert_bytes(M, 4 * 256) == pytest.approx(6.44e9, rel=1e-3)


def test_grouped_product_by_hand():
    # 208 pairs a layer: three matrices a pair, a multiply-add two
    assert cp.expert_flops(M, 208) == 2 * 208 * 3 * D * 512
    # a prompt of 4096 tokens: 8 experts a token in 4 layers, not 256
    assert cp.prefill_expert_flops(M, 4096) == \
        4 * 2 * 4096 * 8 * 3 * D * 512
    assert cp.prefill_expert_flops(M, 4096) * 32 == \
        4 * 2 * 4096 * 256 * 3 * D * 512      # all-experts form: 32 x
    rows = 208 * (2 * D * 2 + 2 * 512 * 4 + 512 * 2 + D * 2)
    assert cp.grouped_product_bytes(M, 568, 208) == \
        568 * 6_291_456 + 4 * rows
