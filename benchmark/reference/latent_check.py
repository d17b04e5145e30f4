"""A served decoder of latent-attention layers with group-routed experts
against its plain reference (`reference/latent_moe.py`): the number
`pattern_check.py` gives for the patterned decoder, for the reason it
gives.

For each sampled request the reference runs once, a layer at a time and
inside a sparse layer a block of the held experts at a time, over the
prompt followed by the tokens the server emitted (teacher forcing):
prefill in the EXPANDED form and every decoded token in the ABSORBED form
through the pages are held against the reference's one full forward
pass.  Weights are drawn again from the seed, a layer or a block at a
time (bf16 values, float32 arithmetic at "highest").

The number compared is the MEAN, over every served position, of the gap
by which the served token's logit lies below the reference's best there:
routing is discrete (a token's 8 experts are the largest of a masked 256
choice scores, and bfloat16 arithmetic flips the closest choices, of
groups too), so the widest gap sees one flipped expert and not a
precision; `pattern_check.py` has the measurements.  With `control` the
same number is read for a lower precision put in the program's place: at
each position the token it puts first.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights, weights_latent
from benchmark.reference import latent_moe as ref

BLOCK = ref.Q_BLOCK      # lengths are padded to whole query blocks
EXPERT_BLOCK = 4

KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "n_shared_experts",
        "n_routed_experts", "router_width", "experts_held",
        "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
        "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
        "topk_group", "num_experts_per_tok", "first_k_dense_replace",
        "rope_theta", "rope_scaling", "vocab_size", "assumed")


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
    def layer(key, sparse, l, x):
        # layer `l` (its number picks its weights' keys and may be
        # traced) is dense or sparse as `sparse` says
        like = m["first_k_dense_replace"] if sparse else 0
        lp = {**weights_latent.attention(key, m, l, bf16),
              **weights_latent.mlp(key, m, l, sparse, bf16, share=None)}
        if sparse:
            lp["experts"] = lambda first, n: weights_latent.experts(
                key, m, l, first, n, bf16)
        x = ref.attention(lp, x, m, precision)
        return ref.mlp(lp, x, m, like, precision, block=EXPERT_BLOCK)

    @jax.jit
    def logits(key, x, start):
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
        return ref.head(weights.lm_embed(key, m, bf16),
                        jnp.ones((m["hidden_size"],), jnp.float32), rows,
                        precision)

    return embed, layer, logits


def reference_logits(key, m: Dict, prompt: Sequence[int],
                     served: Sequence[int], n_out: int,
                     precision: str = "f32") -> np.ndarray:
    """logits [len(served), V] at the positions that chose each served
    token, from one pass over prompt + served[:-1]."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    T = -(-max(len(seq), n_out) // BLOCK) * BLOCK
    padded = np.zeros(T, np.int32)
    padded[:len(seq)] = seq                 # causal: padding changes nothing
    embed, layer, logits = _programs(_model_key(m), T, n_out, precision)
    x = embed(key, jnp.asarray(padded))
    for l in range(m["num_hidden_layers"]):
        x = layer(key, weights_latent.sparse(m, l), jnp.int32(l), x)
    start = min(len(prompt) - 1, T - n_out)
    out = np.asarray(logits(key, x, jnp.int32(start)))
    off = len(prompt) - 1 - start
    return out[off:off + len(served)]


def gaps(key, m: Dict, sample: List[Dict], n_out: int,
         control: str = "") -> np.ndarray:
    """For every served position of the sample, the gap of the token's
    logit below the reference's best.  The token is the one the server
    emitted; with `control` the one that precision puts first at the same
    position."""
    gap = []
    for req in sample:
        lg = reference_logits(key, m, req["prompt"], req["served"], n_out)
        if control:
            chosen = reference_logits(key, m, req["prompt"], req["served"],
                                      n_out, control).argmax(axis=-1)
        else:
            chosen = np.asarray(req["served"])
        gap.append(lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen])
    return np.concatenate(gap)
