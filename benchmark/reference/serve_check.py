"""The served model against the plain reference.

For each sampled request the reference runs once, a layer at a time, over
the prompt followed by the tokens the server emitted (teacher forcing),
and gives its logits at every position that produced a served token.
The number compared is the widest gap by which a served token's logit
lies below the reference's best at its position: 0 where the server chose
what the reference would.  Weights are drawn again from the seed one
layer at a time (the model is bf16 values; the arithmetic is float32 at
"highest"), so the reference never holds more than one layer.

With `control`, `widest_gap` reads the same number for a lower precision
put in the program's place: at each position the token it puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights
from benchmark.reference import transformer as ref

BUCKETS = (64, 256, 1024, 2048, 4096)


@functools.lru_cache(maxsize=None)
def _programs(m_items: tuple, T: int, n_out: int, precision: str):
    m = dict(m_items)

    @jax.jit
    def embed(key, tokens):
        return weights.lm_embed(key, m, jnp.bfloat16)[tokens].astype(
            jnp.float32)

    @jax.jit
    def layer(key, l, x):
        lp = weights.lm_layer(key, m, l, jnp.bfloat16)
        return ref.layer(lp, x, m, precision)

    @jax.jit
    def logits(key, x, start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
        return ref.head(weights.lm_embed(key, m, jnp.bfloat16),
                        jnp.ones((m["hidden_size"],), jnp.float32), rows,
                        precision)

    return embed, layer, logits


def _model_items(m: Dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size",
            "sliding_window", "num_hidden_layers", "rope_theta")
    return tuple((k, m[k]) for k in keys)


def reference_logits(key, m: Dict, prompt: Sequence[int],
                     served: Sequence[int], n_out: int,
                     precision: str = "f32") -> np.ndarray:
    """Logits [len(served), V] at the positions that chose each served
    token, from one pass over prompt + served[:-1]."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = next(b for b in BUCKETS if b >= max(len(seq), n_out))
    padded = np.zeros(T, np.int32)
    padded[:len(seq)] = seq                 # causal: padding changes nothing
    embed, layer, logits = _programs(_model_items(m), T, n_out, precision)
    x = embed(key, jnp.asarray(padded))
    for l in range(m["num_hidden_layers"]):
        x = layer(key, jnp.int32(l), x)
    start = min(len(prompt) - 1, T - n_out)
    out = np.asarray(logits(key, x, jnp.int32(start)))
    off = len(prompt) - 1 - start
    return out[off:off + len(served)]


def widest_gap(key, m: Dict, sample: List[Dict], n_out: int,
               control: str = "") -> float:
    """Widest gap of a token's logit below the reference's best, over
    every served position of the sample.  The token is the one the server
    emitted; with `control` (a lower precision put in the program's
    place) it is the one that precision puts first at the same position
    of the same prompts and tokens."""
    worst = 0.0
    for req in sample:
        lg = reference_logits(key, m, req["prompt"], req["served"], n_out)
        if control:
            chosen = reference_logits(key, m, req["prompt"], req["served"],
                                      n_out, control).argmax(axis=-1)
        else:
            chosen = np.asarray(req["served"])
        gap = lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen]
        worst = max(worst, float(gap.max()))
    return worst
