"""Weights of a retention LM (`family: retention_lm`) made on the device
from `--seed`: the decoder leaves of `lib/weights.py` (same keys, same
draws) and the leaves a power-retention layer adds.  As there, every
layer is drawn from its own key, so the reference draws one at a time.

New leaves: `q_norm` / `k_norm` (per-head RMS norm scales, ones like the
other norms), `w_decay` [D, Hkv] (normal, 1/sqrt(D)) and `b_decay` [Hkv],
the decay gate's bias: `init.decay_bias` of the configuration gives its
first and last value and the kv heads are spread evenly between them, so
that one model holds heads that forget in tens of tokens and heads that
remember a whole document (a zero bias would halve the state at every
token and the state would never matter).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.lib import weights

DECAY_KEY = 101           # apart from LM_LEAVES' 1..7 and the embedding's 0


def decay_bias(m: Dict):
    lo, hi = m["init"]["decay_bias"]
    return jnp.linspace(lo, hi, m["num_key_value_heads"], dtype=jnp.float32)


def layer(key, m: Dict, l, dtype) -> Dict:
    """All of one layer's weights, unstacked."""
    lp = weights.lm_layer(key, m, l, dtype)
    ones = jnp.ones((m["head_dim"],), jnp.float32)
    lp["q_norm"] = {"scale": ones}
    lp["k_norm"] = {"scale": ones}
    k = jax.random.fold_in(jax.random.fold_in(key, DECAY_KEY), l)
    D, Hkv = m["hidden_size"], m["num_key_value_heads"]
    lp["w_decay"] = (jax.random.normal(k, (D, Hkv), jnp.float32)
                     / math.sqrt(D)).astype(dtype)
    lp["b_decay"] = decay_bias(m)
    return lp


def params(key, m: Dict, dtype) -> Dict:
    """The whole tree in the layout `models/transformer.py` serves for
    `attn_kind="retention"`: leaves stacked over layers."""
    n = m["num_hidden_layers"]
    blocks = jax.vmap(lambda l: layer(key, m, l, dtype))(jnp.arange(n))
    return {"embed": weights.lm_embed(key, m, dtype),
            "final_norm": {"scale": jnp.ones((m["hidden_size"],),
                                             jnp.float32)},
            "blocks": blocks}
