"""Parameters, operations and bytes of a decoder of gated short-convolution
and softmax layers with routed experts (`family: conv_moe_lm`), from
shapes alone: the yardstick of `train_mfu` in its cells and of
`expert_gmm_roofline.mfu`.  Branch-free by layer kind; matrix products
only (a multiply-add is two operations); the backward pass is twice the
forward pass; recomputation is never counted.

The routed experts are counted at the BALANCED share: a token's
`num_experts_per_tok` picks fall on the experts held here in the
proportion `held / router_width`, whatever the router did in a run, so
that no routing and no later PR moves `train_mfu`'s yardstick.  The
kernel's roofline (`grouped_*`) takes the pairs the program counted.
"""
from __future__ import annotations

from typing import Dict

from benchmark.lib.counts import DTYPE_BYTES, attended_keys
from benchmark.lib.weights_conv_moe import held, kinds, router_width


def conv_params(m: Dict) -> int:
    """in_proj D x 3D, out_proj D x D, the taps."""
    D = m["hidden_size"]
    return 4 * D * D + D * m["conv_L_cache"]


def attention_params(m: Dict) -> int:
    """q, o; k, v; the two norms a head."""
    D, H, Hkv, d = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return 2 * D * H * d + 2 * D * Hkv * d + 2 * d


def expert_params(m: Dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m: Dict, kind) -> int:
    """A layer: its mixer, its FFN, its two norms (and the router with
    its bias)."""
    D = m["hidden_size"]
    lo, hi = held(m)
    mix = conv_params(m) if kind[0] == "conv" else attention_params(m)
    if kind[1] == "dense":
        ffn = 3 * D * m["intermediate_size"]
    else:
        E = router_width(m)
        ffn = D * E + E + (hi - lo) * expert_params(m)
    return mix + ffn + 2 * D


def param_count(m: Dict) -> int:
    """The tree as the program holds it: tied embedding, final norm."""
    D = m["hidden_size"]
    return (m["vocab_size"] * D + D
            + sum(layer_params(m, k) for k in kinds(m)))


def sparse_layers(m: Dict) -> int:
    return sum(1 for k in kinds(m) if k[1] == "experts")


def mixer_flops_per_token(m: Dict, kind: str, T: int) -> float:
    D, H, Hkv, d = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    if kind == "conv":
        return 2 * D * 3 * D + 2 * D * D
    proj = 2 * D * (H * d + 2 * Hkv * d) + 2 * H * d * D
    return proj + 2 * 2 * H * d * attended_keys(T, 0) / T


def ffn_flops_per_token(m: Dict, kind: str) -> float:
    D = m["hidden_size"]
    if kind == "dense":
        return 6 * D * m["intermediate_size"]
    lo, hi = held(m)
    pairs = m["num_experts_per_tok"] * (hi - lo) / router_width(m)
    return 2 * D * router_width(m) + pairs * 2 * expert_params(m)


def forward_flops_per_token(m: Dict, T: int) -> float:
    """A token of a sequence of T through every layer and the head over
    the vocabulary held."""
    return (sum(mixer_flops_per_token(m, k[0], T)
                + ffn_flops_per_token(m, k[1]) for k in kinds(m))
            + 2 * m["hidden_size"] * m["vocab_size"])


def train_flops_per_sample(m: Dict, traffic: Dict) -> float:
    """Forward and backward of one sequence (backward = 2 x forward)."""
    T = traffic["seq_len"]
    return 3 * T * forward_flops_per_token(m, T)


def grouped_flops(m: Dict, pairs: float) -> float:
    """The nine grouped products of a train step (three forward; the
    rows' and the weights' gradient of each) over `pairs` (token, expert)
    pairs that lay in a group, summed over the sparse layers."""
    return 3 * 2 * pairs * expert_params(m)


def grouped_bytes(m: Dict, pairs: float, dtype: str = "bfloat16") -> float:
    """What those nine products must move, all sparse layers together:
    each of the three passes touches the held experts once (read forward
    and for the rows' gradient, written as the weights' gradient) and
    every row of every product once in and once out: three hidden and
    three expert widths a pair and pass."""
    D, F, b = (m["hidden_size"], m["moe_intermediate_size"],
               DTYPE_BYTES[dtype])
    lo, hi = held(m)
    experts = sparse_layers(m) * (hi - lo) * expert_params(m)
    return 3 * b * (experts + pairs * 3 * (D + F))
