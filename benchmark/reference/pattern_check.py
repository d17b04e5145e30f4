"""A served patterned decoder with routed experts against its plain
reference (`reference/pattern_moe.py`): the number `serve_check.py` gives
for the dense decoder, and what routing's discreteness asks beside it.

For each sampled request the reference runs once, a layer at a time and
inside a sparse layer a block of experts at a time, over the prompt
followed by the tokens the server emitted (teacher forcing).  Weights are
drawn again from the seed, a layer or a block at a time (bf16 values,
float32 arithmetic at "highest").  The number compared is the widest gap
by which a served token's logit lies below the reference's best at its
position.

**Routing is discrete, so the number is a MEAN.**  A token's 8 experts
are the 8 largest of 256 router logits, and with near-uniform scores the
8th and the 9th lie 0.055 apart on average, while bfloat16 arithmetic
moves a router logit by 0.01 (a hidden vector that went through
bfloat16 products and a bfloat16 residual): in about one (token, layer)
pair of seven a sound bfloat16 program picks another 8th expert than the
float32 reference, one of the token's eight experts is then another, its
hidden vector moves by what a whole expert weighs and meets other router
logits in every later layer.  On the chip a sound run's served token is
the reference's first choice at 67 to 69% of positions only, and its
WIDEST gap reads 2.5 to 3.0 (PERF.md section 2), as wide as the float8
control's narrowest: the maximum sees one flipped expert, not a
precision.  Leaving out the positions whose margin between the 8th and
9th logit (found by the reference on its own, `margin` below) is small
repairs the maximum only at a margin of 0.06, which leaves 1.3% of the
positions to compare (at 0.04, 6% of them, three sound runs of five
still read 0.25 to 0.35); the table is printed (`readings ...`) and
decides nothing.  What decides is the MEAN gap over ALL served
positions: a flipped expert moves it by what one position in a thousand
weighs, a sound run reads 0.15 to 0.17, the float8 control 2.0 to 2.1,
and a term left out of every layer moves every position.  What it
cannot see is a fault at few positions: 5% of them a logit of 2 away
reads 0.1 more, still under the limit (PERF.md section 7).

With `control` the same numbers are read for a lower precision put in
the program's place: at each position the token it puts first.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights, weights_pattern
from benchmark.reference import pattern_moe as ref

BLOCK = ref.Q_BLOCK      # lengths are padded to whole query blocks
EXPERT_BLOCK = 16


KEYS = ("hidden_size", "intermediate_size", "num_key_value_heads",
        "head_dim", "vocab_size", "num_hidden_layers", "sliding_window",
        "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size", "moe_routed_scaling_factor",
        "rope_parameters", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer")


def _model_key(m: Dict) -> str:
    """The sizes the programs are made from, as a key they are kept by."""
    return json.dumps({k: m[k] for k in KEYS}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(m_key: str, T: int, n_out: int, precision: str):
    m = json.loads(m_key)
    bf16 = jnp.bfloat16

    @jax.jit
    def embed(key, tokens):
        return weights.lm_embed(key, m, bf16)[tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(key, like, l, x):
        # layer `l` (its number picks its weights' keys and may be
        # traced) has the shapes and kinds of layer `like`
        sparse = weights_pattern.sparse(m, like)
        lp = {**weights_pattern.attention(
                  key, m, l, m["num_attention_heads_per_layer"][like], bf16),
              **weights_pattern.mlp(key, m, l, sparse, bf16, held=None)}
        if sparse:
            lp["experts"] = lambda first, n: weights_pattern.experts(
                key, m, l, first, n, bf16)
        x = ref.attention(lp, x, m, like, precision)
        x, margin = ref.mlp(lp, x, m, like, precision, block=EXPERT_BLOCK)
        return x, (jnp.full((T,), jnp.inf) if margin is None else margin)

    @jax.jit
    def logits(key, x, start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
        return ref.head(weights.lm_embed(key, m, bf16),
                        jnp.ones((m["hidden_size"],), jnp.float32), rows,
                        precision)

    return embed, layer, logits


def reference_logits(key, m: Dict, prompt: Sequence[int],
                     served: Sequence[int], n_out: int,
                     precision: str = "f32") -> Tuple[np.ndarray, np.ndarray]:
    """(logits [len(served), V], margin [len(served)]) at the positions
    that chose each served token, from one pass over prompt +
    served[:-1]; the margin is the least over the sparse layers."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = -(-max(len(seq), n_out) // BLOCK) * BLOCK
    padded = np.zeros(T, np.int32)
    padded[:len(seq)] = seq                 # causal: padding changes nothing
    embed, layer, logits = _programs(_model_key(m), T, n_out, precision)
    x = embed(key, jnp.asarray(padded))
    margin = jnp.full((T,), jnp.inf)
    kinds = list(zip(m["layer_types"], m["mlp_layer_types"],
                     m["num_attention_heads_per_layer"]))
    for l in range(m["num_hidden_layers"]):
        x, here = layer(key, kinds.index(kinds[l]), jnp.int32(l), x)
        margin = jnp.minimum(margin, here)
    start = min(len(prompt) - 1, T - n_out)
    out = np.asarray(logits(key, x, jnp.int32(start)))
    off = len(prompt) - 1 - start
    first = len(prompt) - 1
    return (out[off:off + len(served)],
            np.asarray(margin)[first:first + len(served)])


def gaps(key, m: Dict, sample: List[Dict], n_out: int,
         control: str = "") -> Tuple[np.ndarray, np.ndarray]:
    """For every served position of the sample: (gap of the token's logit
    below the reference's best, the reference's least routing margin
    there).  The token is the one the server emitted; with `control` the
    one that precision puts first at the same position."""
    gap, margin = [], []
    for req in sample:
        lg, mg = reference_logits(key, m, req["prompt"], req["served"],
                                  n_out)
        if control:
            chosen = reference_logits(key, m, req["prompt"], req["served"],
                                      n_out, control)[0].argmax(axis=-1)
        else:
            chosen = np.asarray(req["served"])
        gap.append(lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen])
        margin.append(mg)
    return np.concatenate(gap), np.concatenate(margin)


def widest_gap(gap: np.ndarray, margin: np.ndarray,
               near_tie_margin: float) -> Tuple[float, float]:
    """(the widest gap over the positions whose routing margin is at
    least `near_tie_margin`, the share of positions left out): the
    printed table's numbers."""
    near = margin < near_tie_margin
    kept = gap[~near]
    return (float(kept.max()) if len(kept) else float("nan"),
            float(near.mean()))
