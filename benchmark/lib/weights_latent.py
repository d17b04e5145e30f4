"""Weights of a decoder of latent-attention layers with group-routed
experts (`family: latent_moe_lm`) made on the device from `--seed`:
normal, 1/sqrt(fan_in); norm scales one; the router's bias normal times
`assumed.router_bias_std`.  Every layer's leaf is drawn from its own key,
`fold_in(fold_in(key, leaf), layer)`, and every EXPERT of a layer from
`fold_in` of that and its number among all the layer's experts, so the
reference draws a layer, or one expert, at a time and gets the values the
program's stacked tree holds, and a share of the experts drawn alone
holds what the whole layer holds of them.

Layer l of the run is dense while `l < first_k_dense_replace`, sparse
after.  `layer` gives one layer unstacked, as `reference/latent_moe.py`
takes it; `params` the whole tree in the layout `models/transformer.py`
serves for a layer pattern of one latent kind: `attn["latent"]` stacked
over all layers, `mlp["dense"]` and `mlp["experts"]` over the layers of
that kind of MLP, the experts `experts_held` of `router_width`.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib import weights

LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wi", "wg", "wd",
          "router", "router_bias", "e_wi", "e_wg", "e_wd", "s_wi", "s_wg",
          "s_wd")
LEAF_BASE = 600          # apart from lib/weights*.py's 0..7, 200.., 400..
KIND = "latent"


def sparse(m: Dict, l: int) -> bool:
    return l >= m["first_k_dense_replace"]


def held(m: Dict) -> Tuple[int, int]:
    return tuple(m.get("experts_held") or (0, m["n_routed_experts"]))


def router_width(m: Dict) -> int:
    return m.get("router_width") or m["n_routed_experts"]


def _leaf_key(key, name: str, l):
    return jax.random.fold_in(
        jax.random.fold_in(key, LEAF_BASE + LEAVES.index(name)), l)


def _normal(k, shape, fan_in: int, dtype):
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _ones(n: int) -> Dict:
    return {"scale": jnp.ones((n,), jnp.float32)}


def attention(key, m: Dict, l, dtype) -> Dict:
    """Layer l's attention leaves (`l` may be traced under `vmap`)."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, R = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    k = lambda n: _leaf_key(key, n, l)
    return {"ln1": _ones(D), "q_norm": _ones(Rq), "kv_norm": _ones(R),
            "wq_a": _normal(k("wq_a"), (D, Rq), D, dtype),
            "wq_b": _normal(k("wq_b"), (Rq, H, dn + dr), Rq, dtype),
            "wkv_a": _normal(k("wkv_a"), (D, R + dr), D, dtype),
            "wkv_b": _normal(k("wkv_b"), (R, H, dn + dv), R, dtype),
            "wo": _normal(k("wo"), (H, dv, D), H * dv, dtype)}


def swiglu(key, l, names, D: int, F: int, dtype) -> Dict:
    wi, wg, wd = names
    return {"wi": _normal(_leaf_key(key, wi, l), (D, F), D, dtype),
            "wg": _normal(_leaf_key(key, wg, l), (D, F), D, dtype),
            "wd": _normal(_leaf_key(key, wd, l), (F, D), F, dtype)}


def experts(key, m: Dict, l, first, n: int, dtype) -> Dict:
    """Experts first .. first + n - 1 of layer l, stacked: {wi, wg
    [n, D, F], wd [n, F, D]} (`l` and `first` may be traced)."""
    D, F = m["hidden_size"], m["moe_intermediate_size"]

    def one(e):
        k = lambda n: jax.random.fold_in(_leaf_key(key, n, l), e)
        return {"wi": _normal(k("e_wi"), (D, F), D, dtype),
                "wg": _normal(k("e_wg"), (D, F), D, dtype),
                "wd": _normal(k("e_wd"), (F, D), F, dtype)}

    return jax.vmap(one)(first + jnp.arange(n))


def mlp(key, m: Dict, l, is_sparse: bool, dtype,
        share: Optional[Tuple[int, int]] = ()) -> Dict:
    """Layer l's MLP leaves.  `share` [lo, hi): the experts drawn; ():
    the configuration's `experts_held`; None: none of them (the reference
    then asks `experts` one at a time)."""
    D = m["hidden_size"]
    if not is_sparse:
        return {"ln2": _ones(D), **swiglu(key, l, ("wi", "wg", "wd"), D,
                                          m["intermediate_size"], dtype)}
    E = router_width(m)
    out = {"ln2": _ones(D),
           "router": _normal(_leaf_key(key, "router", l), (D, E), D, dtype),
           "router_bias": jax.random.normal(
               _leaf_key(key, "router_bias", l), (E,), jnp.float32)
           * m["assumed"]["router_bias_std"],
           "shared": swiglu(
               key, l, ("s_wi", "s_wg", "s_wd"), D,
               m["n_shared_experts"] * m["moe_intermediate_size"], dtype)}
    if share is not None:
        lo, hi = share or held(m)
        out["experts"] = experts(key, m, l, lo, hi - lo, dtype)
    return out


def layer(key, m: Dict, l: int, dtype,
          share: Optional[Tuple[int, int]] = ()) -> Dict:
    """All of layer l, unstacked (`l` a Python int)."""
    return {**attention(key, m, l, dtype),
            **mlp(key, m, l, sparse(m, l), dtype, share)}


def params(key, m: Dict, dtype,
           share: Optional[Tuple[int, int]] = ()) -> Dict:
    """The whole tree as the program serves it."""
    n = m["num_hidden_layers"]
    mlps = {}
    for name, want in (("dense", False), ("experts", True)):
        ls = [l for l in range(n) if sparse(m, l) == want]
        if ls:
            mlps[name] = jax.vmap(
                lambda l: mlp(key, m, l, want, dtype, share))(jnp.asarray(ls))
    return {"embed": weights.lm_embed(key, m, dtype),
            "final_norm": _ones(m["hidden_size"]),
            "attn": {KIND: jax.vmap(
                lambda l: attention(key, m, l, dtype))(jnp.arange(n))},
            "mlp": mlps}
