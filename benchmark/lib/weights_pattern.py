"""Weights of a patterned decoder (`family: pattern_moe_lm`) made on the
device from `--seed`: normal, 1/sqrt(fan_in), norm scales one.  Every
layer's leaf is drawn from its own key, `fold_in(fold_in(key, leaf),
layer)`, and every EXPERT of a layer from `fold_in` of that and its
number, so the reference draws a layer, or a block of a layer's experts,
at a time and gets the values the program's stacked tree holds.

`layer` gives one layer unstacked, as `reference/pattern_moe.py` takes it
(its experts all, or a range); `params` the whole tree in the layout
`models/transformer.py` serves for a layer pattern: `attn[kind]` stacked
over the layers of that kind of attention, `mlp["dense"]` and
`mlp["experts"]` over the layers of that kind of MLP.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.lib import weights

LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "wi", "wg", "wd", "router",
          "e_wi", "e_wg", "e_wd", "s_wi", "s_wg", "s_wd")
LEAF_BASE = 200          # apart from lib/weights.py's 0..7 and 1000, 2000


def _leaf_key(key, name: str, l):
    return jax.random.fold_in(
        jax.random.fold_in(key, LEAF_BASE + LEAVES.index(name)), l)


def _normal(k, shape, fan_in: int, dtype):
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def sparse(m: Dict, l: int) -> bool:
    return m["mlp_layer_types"][l] != "dense"


def attention(key, m: Dict, l, heads: int, dtype) -> Dict:
    """Layer l's attention leaves; `heads` its query heads (a layer index
    may be traced under `vmap`, its head count may not)."""
    D, Hkv, d = m["hidden_size"], m["num_key_value_heads"], m["head_dim"]
    k = lambda n: _leaf_key(key, n, l)
    return {"ln1": {"scale": jnp.ones((D,), jnp.float32)},
            "wq": _normal(k("wq"), (D, heads, d), D, dtype),
            "wk": _normal(k("wk"), (D, Hkv, d), D, dtype),
            "wv": _normal(k("wv"), (D, Hkv, d), D, dtype),
            "wo": _normal(k("wo"), (heads, d, D), heads * d, dtype),
            "w_gate": _normal(k("w_gate"), (D, heads), D, dtype)}


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
        held: Optional[Tuple[int, int]] = (0, None)) -> Dict:
    """Layer l's MLP leaves.  `held`: the range of experts to draw, None
    for none of them (the reference then asks `experts` block by block)."""
    D = m["hidden_size"]
    ln2 = {"ln2": {"scale": jnp.ones((D,), jnp.float32)}}
    if not is_sparse:
        return {**ln2, **swiglu(key, l, ("wi", "wg", "wd"), D,
                                m["intermediate_size"], dtype)}
    out = {**ln2,
           "router": _normal(_leaf_key(key, "router", l),
                             (D, m["num_experts"]), D, dtype),
           "shared": swiglu(key, l, ("s_wi", "s_wg", "s_wd"), D,
                            m["shared_expert_intermediate_size"], dtype)}
    if held is not None:
        lo, hi = held
        hi = m["num_experts"] if hi is None else hi
        out["experts"] = experts(key, m, l, lo, hi - lo, dtype)
    return out


def layer(key, m: Dict, l: int, dtype,
          held: Optional[Tuple[int, int]] = (0, None)) -> Dict:
    """All of layer l, unstacked (`l` a Python int)."""
    return {**attention(key, m, l, m["num_attention_heads_per_layer"][l],
                        dtype),
            **mlp(key, m, l, sparse(m, l), dtype, held)}


def params(key, m: Dict, dtype,
           held: Tuple[int, Optional[int]] = (0, None)) -> Dict:
    """The whole tree as the program serves it; `held` the experts here."""
    types, n = m["layer_types"], m["num_hidden_layers"]
    attn, mlps = {}, {}
    for t in dict.fromkeys(types):
        ls = [l for l in range(n) if types[l] == t]
        heads = m["num_attention_heads_per_layer"][ls[0]]
        attn[t] = jax.vmap(lambda l: attention(key, m, l, heads, dtype))(
            jnp.asarray(ls))
    for name, want in (("dense", False), ("experts", True)):
        ls = [l for l in range(n) if sparse(m, l) == want]
        if ls:
            mlps[name] = jax.vmap(
                lambda l: mlp(key, m, l, want, dtype, held))(jnp.asarray(ls))
    return {"embed": weights.lm_embed(key, m, dtype),
            "final_norm": {"scale": jnp.ones((m["hidden_size"],),
                                             jnp.float32)},
            "attn": attn, "mlp": mlps}
