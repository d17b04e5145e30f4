"""Weights of a decoder of gated short-convolution and softmax layers
with routed experts (`family: conv_moe_lm`) made on the device from
`--seed`: normal, 1/sqrt(fan_in); norm scales one; the router's bias
normal times `assumed.router_bias_std`.  Every layer's leaf is drawn from
its own key, `fold_in(fold_in(key, leaf), layer)`, and every EXPERT of a
layer from `fold_in` of that and its number among all the model's experts,
so a share of the experts, or one layer, drawn alone holds the values the
whole tree holds.

Layer l of the run is source layer `first_source_layer + l`; its mixer is
`layer_types` of that, its FFN dense while `l < num_dense_layers`.
`params` gives the tree in the layout `models/transformer.py` trains for a
layer pattern: `attn[kind]` stacked over the layers of that mixer,
`mlp["dense"]` and `mlp["experts"]` over the layers of that FFN, the
experts `experts_held` of `router_width`.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib import weights

LEAVES = ("w_in", "w_conv", "w_out", "wq", "wk", "wv", "wo", "wi", "wg",
          "wd", "router", "router_bias", "e_wi", "e_wg", "e_wd")
LEAF_BASE = 400          # apart from lib/weights*.py's 0..7, 200.., 1000


def kinds(m: Dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every layer run."""
    first = m.get("first_source_layer", 0)
    return [(m["layer_types"][first + l],
             "dense" if l < m["num_dense_layers"] else "experts")
            for l in range(m["num_hidden_layers"])]


def held(m: Dict) -> Tuple[int, int]:
    return tuple(m.get("experts_held") or (0, m["num_experts"]))


def router_width(m: Dict) -> int:
    return m.get("router_width") or m["num_experts"]


def _leaf_key(key, name: str, l):
    return jax.random.fold_in(
        jax.random.fold_in(key, LEAF_BASE + LEAVES.index(name)), l)


def _normal(k, shape, fan_in: float, dtype):
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _ones(n: int):
    return {"scale": jnp.ones((n,), jnp.float32)}


def mixer(key, m: Dict, l, kind: str, dtype) -> Dict:
    """Layer l's token mixer (`l` may be traced, `kind` may not)."""
    D = m["hidden_size"]
    k = lambda n: _leaf_key(key, n, l)
    if kind == "conv":
        taps = m["conv_L_cache"]
        return {"ln1": _ones(D),
                "w_in": _normal(k("w_in"), (D, 3 * D), D, dtype),
                "w_conv": _normal(k("w_conv"), (D, taps), taps, dtype),
                "w_out": _normal(k("w_out"), (D, D), D, dtype)}
    H, Hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    return {"ln1": _ones(D), "q_norm": _ones(d), "k_norm": _ones(d),
            "wq": _normal(k("wq"), (D, H, d), D, dtype),
            "wk": _normal(k("wk"), (D, Hkv, d), D, dtype),
            "wv": _normal(k("wv"), (D, Hkv, d), D, dtype),
            "wo": _normal(k("wo"), (H, d, D), H * d, dtype)}


def experts(key, m: Dict, l, first, n: int, dtype) -> Dict:
    """Experts first .. first + n - 1 of layer l, stacked."""
    D, F = m["hidden_size"], m["moe_intermediate_size"]

    def one(e):
        k = lambda n: jax.random.fold_in(_leaf_key(key, n, l), e)
        return {"wi": _normal(k("e_wi"), (D, F), D, dtype),
                "wg": _normal(k("e_wg"), (D, F), D, dtype),
                "wd": _normal(k("e_wd"), (F, D), F, dtype)}

    return jax.vmap(one)(first + jnp.arange(n))


def mlp(key, m: Dict, l, ffn: str, dtype, share=None) -> Dict:
    """Layer l's FFN; `share` [lo, hi) the experts drawn (None: the
    configuration's `experts_held`)."""
    D = m["hidden_size"]
    k = lambda n: _leaf_key(key, n, l)
    if ffn == "dense":
        F = m["intermediate_size"]
        return {"ln2": _ones(D),
                "wi": _normal(k("wi"), (D, F), D, dtype),
                "wg": _normal(k("wg"), (D, F), D, dtype),
                "wd": _normal(k("wd"), (F, D), F, dtype)}
    lo, hi = share or held(m)
    E = router_width(m)
    std = m["assumed"]["router_bias_std"]
    return {"ln2": _ones(D),
            "router": _normal(k("router"), (D, E), D, dtype),
            "router_bias": jax.random.normal(
                k("router_bias"), (E,), jnp.float32) * std,
            "experts": experts(key, m, l, lo, hi - lo, dtype)}


def params(key, m: Dict, dtype, share=None) -> Dict:
    """The whole tree as the program trains it."""
    ks = kinds(m)
    attn, mlps = {}, {}
    for t in dict.fromkeys(k[0] for k in ks):
        ls = jnp.asarray([l for l, k in enumerate(ks) if k[0] == t])
        attn[t] = jax.vmap(lambda l: mixer(key, m, l, t, dtype))(ls)
    for f in dict.fromkeys(k[1] for k in ks):
        ls = jnp.asarray([l for l, k in enumerate(ks) if k[1] == f])
        mlps[f] = jax.vmap(lambda l: mlp(key, m, l, f, dtype, share))(ls)
    return {"embed": weights.lm_embed(key, m, dtype),
            "final_norm": _ones(m["hidden_size"]),
            "attn": attn, "mlp": mlps}
