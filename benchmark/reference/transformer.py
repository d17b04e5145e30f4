"""Plain reference of the decoder block `models/transformer.py` runs:
pre-norm RMSNorm (eps 1e-6), rotary embedding on interleaved pairs,
grouped-query causal attention under a sliding window, SwiGLU, head tied
to the embedding.  jax.numpy in float32 at matmul precision "highest";
no cache, no batching tricks, nothing imported from the program.

`precision` is what the controls change: "fp8" rounds both operands of
every matrix product to float8_e4m3 (the step below the bfloat16 the
configurations state) and is otherwise the same arithmetic.

Attention is walked one group of key/value heads at a time and each
layer is rematerialised in the backward pass, so that 4096 positions at
the published widths fit beside the optimizer's state.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

EPS = 1e-6


def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(scale, x):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def rope(x, theta: float):
    """x [T, H, Dh]; pairs (0,1), (2,3), ... rotate by position."""
    T, _, Dh = x.shape
    freqs = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def layer(lp: Dict, x, m: Dict, precision: str = "f32"):
    """One block on one sequence: x [T, D] -> [T, D]."""
    T = x.shape[0]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    g = H // Hkv
    window = m.get("sliding_window") or 0
    f32 = lambda w: w.astype(jnp.float32)
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = rope(_mm("td,dhk->thk", h, f32(lp["wq"]), precision), m["rope_theta"])
    k = rope(_mm("td,dhk->thk", h, f32(lp["wk"]), precision), m["rope_theta"])
    v = _mm("td,dhk->thk", h, f32(lp["wv"]), precision)
    i = jnp.arange(T)
    mask = i[None, :] <= i[:, None]
    if window:
        mask &= (i[:, None] - i[None, :]) < window

    def group(args):
        qg, kg, vg = args                       # [T, g, Dh], [T, Dh] x2
        s = _mm("tgd,sd->gts", qg, kg, precision) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return _mm("gts,sd->tgd", p, vg, precision)

    o = jax.lax.map(jax.checkpoint(group), (q.reshape(T, Hkv, g, Dh).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, H, Dh)
    x = x + _mm("thk,hkd->td", o, f32(lp["wo"]), precision)
    h = rmsnorm(lp["ln2"]["scale"], x)
    up = _mm("td,df->tf", h, f32(lp["wi"]), precision)
    gate = jax.nn.silu(_mm("td,df->tf", h, f32(lp["wg"]), precision))
    return x + _mm("tf,fd->td", up * gate, f32(lp["wd"]), precision)


def head(embed, final_scale, x, precision: str = "f32"):
    """x [T, D] -> logits [T, V] against the tied embedding."""
    return _mm("td,vd->tv", rmsnorm(final_scale, x),
               embed.astype(jnp.float32), precision)


def loss(params: Dict, tokens, targets, m: Dict, precision: str = "f32"):
    """Mean next-token cross-entropy over rows [B, T] of a stacked tree."""
    n = m["num_hidden_layers"]
    step = jax.checkpoint(
        functools.partial(layer, m=m, precision=precision))

    def one(row_tokens, row_targets):
        x = params["embed"].astype(jnp.float32)[row_tokens]
        for l in range(n):
            x = step(jax.tree_util.tree_map(lambda p: p[l],
                                            params["blocks"]), x)
        logits = head(params["embed"], params["final_norm"]["scale"], x,
                      precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, row_targets[:, None], -1)[:, 0]
        return jnp.sum(lse - picked)

    total = sum(one(tokens[b], targets[b]) for b in range(tokens.shape[0]))
    return total / tokens.size


def adamw_step(params, grads, mu, nu, count: int, hp: Dict):
    """One plain AdamW step (decoupled decay), as optax.adamw defines it."""
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    tm = jax.tree_util.tree_map
    mu = tm(lambda m_, g: b1 * m_ + (1 - b1) * g, mu, grads)
    nu = tm(lambda v_, g: b2 * v_ + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = tm(lambda p, m_, v_: p - lr * ((m_ / c1)
                                            / (jnp.sqrt(v_ / c2) + eps)
                                            + wd * p), params, mu, nu)
    return params, mu, nu
