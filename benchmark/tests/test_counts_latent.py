"""`lib/counts_latent.py` against parameters, bytes and operations counted
by hand, at GigaChat3.1-702B-A36B's published widths cut to five layers,
a sixteenth of a layer's experts and an eighth of the vocabulary: the
table of the configuration's file, to the last digit."""
import json
import os

import pytest

from benchmark.lib import counts_latent as cl

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "gigachat3.1-702b-a36b-serve.json")) as f:
    M = json.load(f)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _catalog_row():
    """The catalog's row, where the guide is on this machine."""
    if not os.path.exists(CATALOG):
        return None
    with open(CATALOG) as f:
        return next((json.loads(l) for l in f
                     if '"GigaChat3.1-702B-A36B"' in l), None)


ROW = _catalog_row()

D, H, V = 7168, 64, 16032


def test_the_file_is_the_published_row_but_for_reduced():
    assert (M["hidden_size"], M["num_attention_heads"], M["q_lora_rank"],
            M["kv_lora_rank"], M["qk_nope_head_dim"], M["qk_rope_head_dim"],
            M["v_head_dim"]) == (D, H, 1536, 512, 128, 64, 192)
    assert (M["intermediate_size"], M["moe_intermediate_size"],
            M["router_width"], M["n_group"], M["topk_group"],
            M["num_experts_per_tok"], M["routed_scaling_factor"]) == \
        (18432, 2048, 256, 8, 4, 8, 2.5)
    assert M["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"} and M["rope_theta"] == 100000
    assert set(M["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "tie_word_embeddings", "num_nextn_predict_layers"}
    for key, cut in M["reduced"].items():
        assert M[key] == cut["here"] and cut["why"]
    assert M["experts_held"] == [0, 16] and M["first_source_layer"] == 2
    if ROW is not None:                  # every other key as the catalog's
        assert M["source"] == ROW["source_url"]
        for key, value in ROW["config"].items():
            if key in M["reduced"]:
                assert M["reduced"][key]["source"] == value
            else:
                assert M[key] == value, key


def test_attention_by_hand():
    q_a, q_b = D * 1536, 1536 * H * (128 + 64)
    kv_a, kv_b = D * (512 + 64), 512 * H * (128 + 192)
    o = H * 192 * D
    assert (q_b, kv_b, o) == (1536 * 12288, 512 * 20480, 12288 * D)
    assert cl.attention_params(M) == q_a + q_b + kv_a + kv_b + o \
        + 1536 + 512 == 132_581_376
    assert cl.attention_params(M) == pytest.approx(132.58e6, rel=1e-4)


def test_mlps_by_hand():
    assert cl.dense_mlp_params(M) == 3 * D * 18432 == 396_361_728
    assert cl.expert_params(M) == 3 * D * 2048 == 44_040_192
    assert cl.expert_params(M) * 2 == pytest.approx(88.08e6, rel=1e-4)
    assert 256 * cl.expert_params(M) == pytest.approx(11.27e9, rel=1e-3)
    assert cl.shared_params(M) == 44_040_192
    assert cl.router_params(M) == D * 256 + 256 + 2 * D == 1_849_600
    assert cl.embedding_params(M) == V * D == 114_917_376
    assert cl.sparse_layers(M) == 4


def test_weights_are_4_176_b_and_8_35_gb():
    dense = 132_581_376 + 396_361_728 + 2 * D
    sparse = 132_581_376 + 44_040_192 + 1_849_600 + 16 * 44_040_192
    assert cl.dense_layer_params(M) == dense == 528_957_440
    assert cl.sparse_layer_params_outside_experts(M) \
        + 16 * cl.expert_params(M) == sparse == 883_114_240
    total = dense + 4 * sparse + V * D + D
    assert cl.param_count(M) == total == 4_176_338_944
    assert total == pytest.approx(4.176e9, rel=1e-4)
    assert 2 * total == pytest.approx(8.35e9, rel=1e-3)
    assert 4 * 16 * 44_040_192 / total == pytest.approx(0.675, abs=1e-3)
    assert cl.params_outside_experts(M) == total - 64 * 44_040_192
    assert 2 * cl.params_outside_experts(M) == pytest.approx(2.72e9,
                                                             rel=2e-3)


def test_decode_step_bytes_by_hand():
    assert cl.cache_bytes_per_token_layer(M) == (512 + 64) * 2 == 1152
    w = 2 * cl.params_outside_experts(M)
    assert cl.decode_step_bytes(M, 0, 0) == w
    # 10 rows of 9000 tokens, 5 of the 16 held experts hit a sparse layer
    got = cl.decode_step_bytes(M, 4 * 5, 90000)
    assert got == w + 20 * 88_080_384 + 5 * 90000 * 1152
    assert got == pytest.approx(5.0e9, rel=0.01)


def test_the_read_of_the_cache_by_hand():
    # a head's score over 576 numbers and its sum of 512, a multiply-add
    # two: 139264 operations a token and layer, 121 a byte
    assert cl.attn_read_flops_per_token(M) == 64 * 2 * (576 + 512) \
        == 139_264
    assert 139_264 / 1152 == pytest.approx(120.9, abs=0.1)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # under the ridge of 240: the bytes bound it
    assert cl.attn_read_seconds(M, 90000, peaks) == \
        pytest.approx(90000 * 1152 / 819e9)
    slow = dict(peaks, bf16_flops_per_s=50e12)      # a ridge of 61
    assert cl.attn_read_seconds(M, 90000, slow) == \
        pytest.approx(90000 * 139_264 / 50e12)


def test_grouped_product_by_hand():
    assert cl.expert_flops(M, 12) == 2 * 12 * 3 * D * 2048
    rows = 12 * (2 * D * 2 + 2 * 2048 * 4 + 2048 * 2 + D * 2)
    assert cl.grouped_product_bytes(M, 20, 12) == 20 * 88_080_384 + rows
