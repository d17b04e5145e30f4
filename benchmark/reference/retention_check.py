"""A served retention LM against its plain reference: the same number as
`serve_check.py` gives for the softmax decoder.

For each sampled request the reference (`reference/retention.py`, the
attention form: no state, no chunks) runs once, a layer at a time, over
the prompt followed by the tokens the server emitted (teacher forcing).
The number compared is the widest gap by which a served token's logit
lies below the reference's best at its position: 0 where the server chose
what the reference would.  Weights are drawn again from the seed one
layer at a time (bf16 values, float32 arithmetic at "highest").

With `control` the same number is read for a lower precision put in the
program's place: at each position the token it puts first.

That number moves only where a token changes, and a token rarely changes
with the precision the STATE is held in: a bfloat16 state reads 0.095 to
0.100 against sound runs' 0.069 at most (PERF.md, PR 28).  So a second
number, `state_error`: the longest sampled prompt is served once more
and the logits its decode steps gave, each read out of the state after
one more update, are held against the reference's at the same positions:
the relative error of the later half of them together, where what the
updates lose has added up.  Its control is the program itself with the
state held in bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights, weights_retention
from benchmark.reference import retention as ref

BLOCK = ref.Q_BLOCK      # lengths are padded to whole query blocks

KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size",
        "num_hidden_layers", "rope_theta")


@functools.lru_cache(maxsize=None)
def _programs(m_items: tuple, T: int, n_out: int, precision: str):
    m = dict(m_items)
    m["init"] = {"decay_bias": m.pop("decay_bias")}

    @jax.jit
    def embed(key, tokens):
        return weights.lm_embed(key, m, jnp.bfloat16)[tokens].astype(
            jnp.float32)

    @jax.jit
    def layer(key, l, x):
        return ref.layer(weights_retention.layer(key, m, l, jnp.bfloat16),
                         x, m, precision)

    @jax.jit
    def logits(key, x, start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
        return ref.head(weights.lm_embed(key, m, jnp.bfloat16),
                        jnp.ones((m["hidden_size"],), jnp.float32), rows,
                        precision)

    return embed, layer, logits


def _model_items(m: Dict) -> tuple:
    return tuple((k, m[k]) for k in KEYS) + (
        ("decay_bias", tuple(m["init"]["decay_bias"])),)


def reference_logits(key, m: Dict, prompt: Sequence[int],
                     served: Sequence[int], n_out: int,
                     precision: str = "f32") -> np.ndarray:
    """Logits [len(served), V] at the positions that chose each served
    token, from one pass over prompt + served[:-1]."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = -(-max(len(seq), n_out) // BLOCK) * BLOCK
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


def state_error(key, m: Dict, prompt: Sequence[int], tokens: Sequence[int],
                logits: np.ndarray, n_out: int) -> float:
    """|logits - reference| / |reference| over the later half of the
    positions and the whole vocabulary, for `logits` [len(tokens) - 1,
    V]: what the program's decode steps gave when they chose `tokens[1:]`
    behind `prompt`.  (The first token comes from the prefill's own
    logits, before any update of the state.)"""
    want = reference_logits(key, m, prompt, tokens, n_out)[1:]
    half = len(want) // 2
    return float(np.linalg.norm(np.asarray(logits, np.float32)[half:]
                                - want[half:])
                 / np.linalg.norm(want[half:]))


def widest_gap(key, m: Dict, sample: List[Dict], n_out: int,
               control: str = "") -> float:
    """Widest gap of a token's logit below the reference's best, over
    every served position of the sample.  The token is the one the server
    emitted; with `control` it is the one that precision puts first at
    the same position of the same prompts and tokens."""
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
