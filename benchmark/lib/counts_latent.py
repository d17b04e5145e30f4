"""Parameters, bytes and operations of a decoder of latent-attention
layers with group-routed experts of which a share is held, from shapes
alone: the yardstick of `latent_step_roofline_traced.tpot`,
`latent_attn_roofline_traced.tpot` and `held_gmm_roofline_traced.tpot`.

A decode step must read every weight OUTSIDE the routed experts once, the
three matrices of each DISTINCT held expert that some token of the step
chose (the program counts them, a layer; an expert nobody chose need not
be read), and the live cache: one latent of `kv_lora_rank` and one
shared key of `qk_rope_head_dim` a token and layer, whatever the heads.
The same work whatever implements the step.
"""
from __future__ import annotations

from typing import Dict

from benchmark.lib.counts import DTYPE_BYTES


def attention_params(m: Dict) -> int:
    """q_a, q_b, kv_a, kv_b, o and the two norms inside the layer (of the
    query's rank and of the latent's)."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, R = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (D * Rq + Rq * H * (dn + dr) + D * (R + dr)
            + R * H * (dn + dv) + H * dv * D + Rq + R)


def dense_mlp_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: Dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: Dict) -> int:
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: Dict) -> int:
    """The router, its bias and the layer's two norms of the hidden
    width."""
    E = m.get("router_width") or m["n_routed_experts"]
    return m["hidden_size"] * E + E + 2 * m["hidden_size"]


def embedding_params(m: Dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def sparse_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def dense_layer_params(m: Dict) -> int:
    """Attention, the dense SwiGLU and the layer's two norms."""
    return attention_params(m) + dense_mlp_params(m) + 2 * m["hidden_size"]


def sparse_layer_params_outside_experts(m: Dict) -> int:
    return attention_params(m) + shared_params(m) + router_params(m)


def params_outside_experts(m: Dict) -> int:
    """The tied embedding, the final norm and every layer's attention,
    norms, dense MLP or router and shared expert."""
    return (embedding_params(m) + m["hidden_size"]
            + m["first_k_dense_replace"] * dense_layer_params(m)
            + sparse_layers(m) * sparse_layer_params_outside_experts(m))


def param_count(m: Dict) -> int:
    """Everything this chip holds: `n_routed_experts` experts a sparse
    layer."""
    return (params_outside_experts(m)
            + sparse_layers(m) * m["n_routed_experts"] * expert_params(m))


def cache_bytes_per_token_layer(m: Dict, dtype: str = "bfloat16") -> int:
    """The latent and the shared key one cached token holds in a layer."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * DTYPE_BYTES[dtype]


def expert_bytes(m: Dict, experts_hit: float,
                 dtype: str = "bfloat16") -> float:
    """`experts_hit`: distinct experts read, summed over sparse layers."""
    return experts_hit * expert_params(m) * DTYPE_BYTES[dtype]


def decode_step_bytes(m: Dict, experts_hit: float, live_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """What one decode step must read.  `experts_hit`: distinct held
    experts chosen, summed over the sparse layers; `live_tokens`: sum over
    the active rows of their depth."""
    return (params_outside_experts(m) * DTYPE_BYTES[dtype]
            + expert_bytes(m, experts_hit, dtype)
            + m["num_hidden_layers"] * live_tokens
            * cache_bytes_per_token_layer(m, dtype))


def attn_read_flops_per_token(m: Dict) -> int:
    """The absorbed read's operations a cached token and layer: every
    head's score over latent and shared key, and its sum of latents."""
    R, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return m["num_attention_heads"] * 2 * ((R + dr) + R)


def attn_read_seconds(m: Dict, live_tokens: float, peaks: Dict,
                      dtype: str = "bfloat16") -> float:
    """The least one layer's read of `live_tokens` cached tokens could
    take: the larger of their bytes and their operations."""
    return live_tokens * max(
        cache_bytes_per_token_layer(m, dtype) / peaks["hbm_bytes_per_s"],
        attn_read_flops_per_token(m) / peaks["bf16_flops_per_s"])


def expert_flops(m: Dict, pairs: float) -> float:
    """The routed experts' operations for `pairs` (token, expert) pairs:
    three matrices a pair, a multiply-add two."""
    return 2 * pairs * expert_params(m)


def grouped_product_bytes(m: Dict, experts_hit: float, pairs_here: float,
                          dtype: str = "bfloat16") -> float:
    """What the three grouped products of the sparse layers must move a
    pass: the distinct held experts chosen (summed over the layers, as
    `experts_hit` is), and for `pairs_here` pairs (summed likewise) the
    rows in (hidden, twice; the gated width once) and out (the expert
    width twice in float32, the hidden width once)."""
    D, F, b = (m["hidden_size"], m["moe_intermediate_size"],
               DTYPE_BYTES[dtype])
    return (expert_bytes(m, experts_hit, dtype)
            + pairs_here * (2 * D * b + 2 * F * 4 + F * b + D * b))
