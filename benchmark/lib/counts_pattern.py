"""Parameters, bytes and operations of a patterned decoder with routed
experts, from shapes alone: the yardstick of
`moe_step_roofline_traced.tpot` and of `expert_gmm_roofline_traced.tpot`.

A decode step must read every weight OUTSIDE the routed experts once, the
three matrices of each DISTINCT expert that some token of the step chose
(the program counts them, a layer; an expert nobody chose need not be
read), and the live cache: a full-attention layer holds a row's whole
context, a sliding layer its last `sliding_window` tokens at most.  The
same work whatever implements the step.
"""
from __future__ import annotations

from typing import Dict

from benchmark.lib.counts import DTYPE_BYTES


def _layers(m: Dict):
    n = m["num_hidden_layers"]
    return list(zip(m["layer_types"][:n], m["mlp_layer_types"][:n],
                    m["num_attention_heads_per_layer"][:n]))


def attention_params(m: Dict, heads: int) -> int:
    """q, o; k, v; the gate a head; the layer's first norm."""
    D, d, Hkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    return 2 * D * heads * d + 2 * D * Hkv * d + D * heads + D


def expert_params(m: Dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def mlp_params_outside_experts(m: Dict, kind: str) -> int:
    """The second norm and: the dense SwiGLU, or the router and the
    shared expert."""
    D = m["hidden_size"]
    if kind == "dense":
        return D + 3 * D * m["intermediate_size"]
    return (D + D * m["num_experts"]
            + 3 * D * m["shared_expert_intermediate_size"])


def sparse_layers(m: Dict) -> int:
    return sum(1 for _, k, _ in _layers(m) if k != "dense")


def params_outside_experts(m: Dict) -> int:
    """The tied embedding, the final norm and every layer's attention,
    norms, dense MLP or router and shared expert."""
    return (m["vocab_size"] * m["hidden_size"] + m["hidden_size"]
            + sum(attention_params(m, h)
                  + mlp_params_outside_experts(m, k)
                  for _, k, h in _layers(m)))


def param_count(m: Dict, experts_held: int = None) -> int:
    held = m["num_experts"] if experts_held is None else experts_held
    return (params_outside_experts(m)
            + sparse_layers(m) * held * expert_params(m))


def cache_bytes_per_token_layer(m: Dict, dtype: str = "bfloat16") -> int:
    """Keys and values one cached token holds in one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * DTYPE_BYTES[dtype]


def expert_bytes(m: Dict, experts_hit: float,
                 dtype: str = "bfloat16") -> float:
    """`experts_hit`: distinct experts read, summed over sparse layers."""
    return experts_hit * expert_params(m) * DTYPE_BYTES[dtype]


def decode_step_bytes(m: Dict, experts_hit: float, full_tokens: float,
                      ring_tokens: float, dtype: str = "bfloat16") -> float:
    """What one decode step must read.  `experts_hit`: distinct experts
    chosen, summed over the sparse layers.  `full_tokens`: sum over the
    active rows of their depth; `ring_tokens`: sum over them of
    min(depth, sliding_window)."""
    kinds = [t for t, _, _ in _layers(m)]
    per = cache_bytes_per_token_layer(m, dtype)
    return (params_outside_experts(m) * DTYPE_BYTES[dtype]
            + expert_bytes(m, experts_hit, dtype)
            + kinds.count("full_attention") * full_tokens * per
            + kinds.count("sliding_attention") * ring_tokens * per)


def expert_flops(m: Dict, pairs: int) -> int:
    """The routed experts' operations for `pairs` (token, expert) pairs
    of one layer: three matrices a pair, a multiply-add two."""
    return 2 * pairs * expert_params(m)


def prefill_expert_flops(m: Dict, T: int) -> int:
    """All sparse layers of one prompt of T tokens: the routed pairs'
    operations and nothing over experts a token did not choose."""
    return sparse_layers(m) * expert_flops(m, T * m["num_experts_per_tok"])


def grouped_product_bytes(m: Dict, experts_hit: float, pairs: float,
                          dtype: str = "bfloat16") -> float:
    """What the three grouped products of every sparse layer must move a
    pass: the distinct experts chosen (summed over the layers, as
    `experts_hit` is), and for `pairs` (token, expert) pairs a layer the
    rows in (hidden, twice; the gated width once) and out (the expert
    width twice in float32, the hidden width once)."""
    D, F, b = (m["hidden_size"], m["moe_intermediate_size"],
               DTYPE_BYTES[dtype])
    rows = pairs * (2 * D * b + 2 * F * 4 + F * b + D * b)
    return expert_bytes(m, experts_hit, dtype) + sparse_layers(m) * rows
