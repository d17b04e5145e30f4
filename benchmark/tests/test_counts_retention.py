"""`lib/counts_retention.py` against bytes and operations counted by
hand."""
import pytest

from benchmark.lib import counts, counts_retention as cr

BRUMBY8 = dict(hidden_size=5120, num_attention_heads=40,
               num_key_value_heads=8, head_dim=128, intermediate_size=17408,
               vocab_size=151936, num_hidden_layers=8)


def test_state_by_hand():
    assert cr.state_features(BRUMBY8) == 128 * 129 // 2 == 8256
    # a layer and row: 8 kv heads x 8256 x 128 float32 = 33.8 MB of state
    # and 8 x 8256 float32 = 0.26 MB of normaliser
    state, norm = 8 * 8256 * 128 * 4, 8 * 8256 * 4
    assert state == 33_816_576 and norm == 264_192
    assert cr.state_bytes_per_row(BRUMBY8) == 8 * (state + norm)
    assert cr.state_bytes_per_row(BRUMBY8) == pytest.approx(272.6e6,
                                                            rel=1e-3)
    # held in bfloat16 the state halves; the normaliser stays float32
    assert cr.state_bytes_per_row(BRUMBY8, "bfloat16") == \
        8 * (state // 2 + norm)


def test_params_by_hand():
    layer = (5120 * 5120 * 2 + 5120 * 1024 * 2      # q, o; k, v
             + 3 * 5120 * 17408 + 2 * 5120)         # MLP; two norms
    extra = 5120 * 8 + 8 + 2 * 128                  # gate, bias; q/k norms
    want = 8 * (layer + extra) + 151936 * 5120 + 5120
    assert cr.param_count(BRUMBY8) == want
    assert cr.param_count(BRUMBY8) - counts.lm_param_count(BRUMBY8) == \
        8 * extra
    assert want * 2 == pytest.approx(6.84e9, rel=2e-3)    # bf16 bytes


def test_decode_step_bytes_by_hand():
    w = cr.param_count(BRUMBY8) * 2
    row = cr.state_bytes_per_row(BRUMBY8)
    # weights once; each active row's state read and written
    assert cr.decode_step_bytes(BRUMBY8, 0) == w
    assert cr.decode_step_bytes(BRUMBY8, 16) == w + 16 * 2 * row
    assert cr.decode_step_bytes(BRUMBY8, 16) == pytest.approx(15.57e9,
                                                              rel=2e-3)
    assert cr.decode_step_bytes(BRUMBY8, 9.5) == w + 19 * row


def test_prefill_flops_by_hand():
    m = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             head_dim=4, intermediate_size=16, vocab_size=32,
             num_hidden_layers=3)
    T, D = 5, 10                                    # 4 * 5 / 2 features
    proj = 2 * 8 * (8 + 4 + 4 + 1) + 2 * 8 * 8      # q, k, v, gate; o
    mlp = 3 * 2 * 8 * 16
    state = 2 * (1 + 2) * (D * 4 + D)     # update a kv head, read a q head
    assert cr.prefill_flops(m, T) == \
        3 * T * (proj + mlp + state) + 2 * 8 * 32
