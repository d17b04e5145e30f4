"""Operation and byte counts against cases worked by hand."""
import pytest

from benchmark.lib import counts

MISTRAL = dict(hidden_size=4096, num_attention_heads=32,
               num_key_value_heads=8, head_dim=128, intermediate_size=14336,
               vocab_size=32000, sliding_window=4096, num_hidden_layers=32)


def test_attended_keys():
    assert counts.attended_keys(4, 0) == 1 + 2 + 3 + 4
    assert counts.attended_keys(6, 2) == 1 + 2 + 2 + 2 + 2 + 2
    assert counts.attended_keys(4096, 4096) == 4096 * 4097 // 2


def test_lm_forward_by_hand():
    m = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             head_dim=4, intermediate_size=16, vocab_size=32,
             sliding_window=0, num_hidden_layers=3)
    T = 5
    proj = 2 * 8 * (8 + 4 + 4) + 2 * 8 * 8      # q, k, v; o
    mlp = 3 * 2 * 8 * 16
    attn = 2 * 2 * (2 * 4) * (1 + 2 + 3 + 4 + 5)   # scores and values
    want = 3 * (T * (proj + mlp) + attn) + T * 2 * 8 * 32
    assert counts.lm_forward_flops(m, T) == want
    assert counts.lm_train_flops(m, T) == 3 * want


def test_mistral_sizes():
    # 7.24 B parameters with separate head; tied here: minus 131 M
    assert counts.lm_param_count(MISTRAL) == pytest.approx(7.24e9 - 0.131e9,
                                                           rel=3e-3)
    m16 = dict(MISTRAL, num_hidden_layers=16)
    assert counts.kv_bytes_per_token(m16) == 2 * 8 * 128 * 16 * 2 == 65536
    w = counts.lm_param_count(m16) * 2
    assert counts.decode_step_bytes(m16, 1000) == w + 1000 * 65536


def test_resnet50_by_hand():
    m = dict(depth=50, image_size=224, num_classes=1000)
    fwd = counts.resnet_forward_flops(m)
    # the well-known 4.1 G multiply-adds of ResNet-50 v1.5 at 224 px
    assert fwd / 2 == pytest.approx(4.09e9, rel=5e-3)
    stem = 112 * 112 * 7 * 7 * 3 * 64
    assert counts._conv_macs(112, 7, 3, 64) == stem
    assert counts.resnet_train_flops(m) == 3 * fwd
