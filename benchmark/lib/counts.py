"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick of `train_mfu` and of the roofline shares, so
they live here where no later PR can move them.  A multiply-add is two
operations.  Recomputation is never counted; the backward pass is twice
the forward pass's matrix products.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def attended_keys(T: int, window: int) -> int:
    """Sum over query positions of the keys a causal mask with a sliding
    window lets each see: position t sees min(t + 1, window)."""
    if not window or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def lm_forward_flops(m: Dict, T: int) -> int:
    """One sequence of T tokens through every layer and the head."""
    D, H, Hkv, Dh, F, V, L = (
        m["hidden_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
        m["vocab_size"], m["num_hidden_layers"])
    proj = 2 * D * (H * Dh + 2 * Hkv * Dh) + 2 * H * Dh * D
    mlp = 3 * 2 * D * F
    attn = 2 * 2 * H * Dh * attended_keys(T, m.get("sliding_window") or 0)
    return L * (T * (proj + mlp) + attn) + T * 2 * D * V


def lm_train_flops(m: Dict, T: int) -> int:
    """Forward and backward of one sequence (backward = 2 x forward)."""
    return 3 * lm_forward_flops(m, T)


def lm_param_count(m: Dict) -> int:
    """Parameters of the tree as this repo holds it (head tied)."""
    D, H, Hkv, Dh, F, V, L = (
        m["hidden_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
        m["vocab_size"], m["num_hidden_layers"])
    per_layer = D * (H * Dh + 2 * Hkv * Dh) + H * Dh * D + 3 * D * F + 2 * D
    return L * per_layer + V * D + D


def kv_bytes_per_token(m: Dict, dtype: str = "bfloat16") -> int:
    """Keys and values one cached token holds over all layers."""
    return (2 * m["num_key_value_heads"] * m["head_dim"]
            * m["num_hidden_layers"] * DTYPE_BYTES[dtype])


def decode_step_bytes(m: Dict, live_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """What one decode step must read: every weight once (the tied
    embedding once, as the head) and the live rows of the cache."""
    return (lm_param_count(m) * DTYPE_BYTES[dtype]
            + live_tokens * kv_bytes_per_token(m, dtype))


def _conv_macs(hw_out: int, k: int, cin: int, cout: int) -> int:
    return hw_out * hw_out * k * k * cin * cout


def resnet_forward_flops(m: Dict) -> int:
    """One image through the convolutions and the classifier of ResNet
    v1.5 (bottleneck blocks, the stride on the 3x3); batch norm, ReLU and
    pooling are not matrix work and are left out."""
    from benchmark.lib.weights import resnet_blocks
    hw = m["image_size"] // 2
    macs = _conv_macs(hw, 7, 3, 64)
    hw //= 2                                  # 3x3/2 max pool
    for _, cin, w, stride in resnet_blocks(m):
        out_hw = hw // stride
        macs += _conv_macs(hw, 1, cin, w)            # 1x1 before the stride
        macs += _conv_macs(out_hw, 3, w, w)          # the 3x3 carries it
        macs += _conv_macs(out_hw, 1, w, 4 * w)
        if stride != 1 or cin != 4 * w:
            macs += _conv_macs(out_hw, 1, cin, 4 * w)
        hw = out_hw
    return 2 * (macs + 4 * w * m["num_classes"])


def resnet_train_flops(m: Dict) -> int:
    return 3 * resnet_forward_flops(m)


def train_flops_per_sample(config: Dict, traffic: Dict) -> int:
    """Dispatch on the model family the configuration names."""
    if config["family"] == "resnet":
        return resnet_train_flops(config)
    return lm_train_flops(config, traffic["seq_len"])
