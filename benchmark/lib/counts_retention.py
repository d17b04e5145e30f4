"""Bytes and operations a power-retention LM needs, from shapes alone:
the yardstick of `state_step_roofline_traced.tpot`.

A retention layer's cache is, per row and kv head, a state of D x d_head
numbers and a normaliser of D, D = d_head (d_head + 1) / 2: the distinct
entries of the symmetric square (the program may hold more, padded to
its tiles; what it must move is counted here).  A decode step must read
every weight once and, for each ACTIVE row, read and write the row's
whole state and normaliser; the tokens behind the row cost nothing.
"""
from __future__ import annotations

from typing import Dict

from benchmark.lib.counts import DTYPE_BYTES, lm_param_count


def state_features(m: Dict) -> int:
    d = m["head_dim"]
    return d * (d + 1) // 2


def state_bytes_per_row(m: Dict, state_dtype: str = "float32") -> int:
    """One row's states (in `state_dtype`) and normalisers (float32)
    over all layers."""
    D, d = state_features(m), m["head_dim"]
    per_head = D * d * DTYPE_BYTES[state_dtype] + D * DTYPE_BYTES["float32"]
    return m["num_hidden_layers"] * m["num_key_value_heads"] * per_head


def param_count(m: Dict) -> int:
    """The decoder's parameters (head tied) and, a layer, the decay gate
    (hidden x kv heads, and its bias) and the two per-head norm scales."""
    gate = m["hidden_size"] * m["num_key_value_heads"] \
        + m["num_key_value_heads"]
    return lm_param_count(m) + m["num_hidden_layers"] * (
        gate + 2 * m["head_dim"])


def decode_step_bytes(m: Dict, active_rows: float,
                      weights_dtype: str = "bfloat16",
                      state_dtype: str = "float32") -> float:
    """What one decode step must move: the weights once, and the state
    and normaliser of every active row read and written."""
    return (param_count(m) * DTYPE_BYTES[weights_dtype]
            + active_rows * 2 * state_bytes_per_row(m, state_dtype))


def prefill_flops(m: Dict, T: int) -> int:
    """One prompt of T tokens through every layer in the recurrent form,
    and the head once (a prefill needs the last position's logits only).
    A token and layer: the projections and the gate, the MLP, phi(k) v^T
    into the state and the normaliser (a kv head), the read-out by phi(q)
    of both (a query head).  A multiply-add is two operations."""
    Dm, H, Hkv, d, F, V, L = (
        m["hidden_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
        m["vocab_size"], m["num_hidden_layers"])
    D = state_features(m)
    proj = 2 * Dm * (H * d + 2 * Hkv * d + Hkv) + 2 * H * d * Dm
    mlp = 3 * 2 * Dm * F
    state = 2 * (Hkv + H) * (D * d + D)
    return L * T * (proj + mlp + state) + 2 * Dm * V
