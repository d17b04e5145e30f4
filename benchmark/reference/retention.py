"""Plain reference of a power-retention decoder block (Brumby-14B-Base as
`benchmark/configs/brumby-14b-serve.json` states it): pre-norm RMSNorm,
per-head RMS norm of q and k with a learned scale, rotary embedding on
interleaved pairs, then the ATTENTION FORM of retention with power 2:

    gamma_t = log sigmoid(W_g h_t + b_g)              one a kv head
    a_tj    = exp(sum_{l=j+1..t} gamma_l) * (q_t . k_j / sqrt(d)) ** 2
    y_t     = sum_{j<=t} a_tj v_j / (sum_{j<=t} a_tj + eps)

and SwiGLU, head tied to the embedding.  No softmax, no state, no
chunks, no cache: every query meets every earlier key.  jax.numpy in
float32 at matmul precision "highest"; nothing imported from the
program.  Queries are walked in blocks of `Q_BLOCK` so that 12544
positions at the published widths fit the chip (a [40, 512, 12544]
float32 block of weights is 1 GB).

`precision` "fp8" is the control: both operands of every matrix product
rounded to float8_e4m3, otherwise the same arithmetic.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference.transformer import _mm, head, rmsnorm, rope  # noqa: F401

EPS = 1e-6          # the normaliser's, as the configuration assumes
Q_BLOCK = 512


def retention(q, k, v, gamma, precision: str = "f32",
              q_block: int = Q_BLOCK):
    """q [T, H, d], k and v [T, Hkv, d], gamma [T, Hkv] (<= 0) ->
    y [T, H, d].  T a multiple of `q_block`, or under it."""
    T, H, d = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    q_block = min(q_block, T)
    cum = jnp.cumsum(gamma, axis=0)                        # [T, Hkv]
    qb = q.reshape(T // q_block, q_block, Hkv, g, d)
    j = jnp.arange(T)

    def block(args):
        qs, start = args                       # [Tb, Hkv, g, d], scalar
        t = start + jnp.arange(q_block)
        w = _mm("thgd,shd->hgts", qs, k, precision) / math.sqrt(d)
        cum_t = jax.lax.dynamic_slice_in_dim(cum, start, q_block, 0)
        left = cum_t.T[:, :, None] - cum.T[:, None, :]     # [Hkv, Tb, T]
        left = jnp.where(j[None, None, :] <= t[None, :, None], left,
                         -jnp.inf)
        a = jnp.square(w) * jnp.exp(left)[:, None]         # [Hkv,g,Tb,T]
        num = _mm("hgts,shd->thgd", a, v, precision)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)       # [Tb, Hkv, g]
        return num / (den[..., None] + EPS)

    y = jax.lax.map(block, (qb, jnp.arange(0, T, q_block)))
    return y.reshape(T, H, d)


def layer(lp: Dict, x, m: Dict, precision: str = "f32"):
    """One block on one sequence: x [T, D] -> [T, D]."""
    f32 = lambda w: w.astype(jnp.float32)
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = _mm("td,dhk->thk", h, f32(lp["wq"]), precision)
    k = _mm("td,dhk->thk", h, f32(lp["wk"]), precision)
    v = _mm("td,dhk->thk", h, f32(lp["wv"]), precision)
    q = rope(rmsnorm(lp["q_norm"]["scale"], q), m["rope_theta"])
    k = rope(rmsnorm(lp["k_norm"]["scale"], k), m["rope_theta"])
    gamma = jax.nn.log_sigmoid(
        _mm("td,dh->th", h, f32(lp["w_decay"]), precision)
        + f32(lp["b_decay"]))
    y = retention(q, k, v, gamma, precision)
    x = x + _mm("thk,hkd->td", y, f32(lp["wo"]), precision)
    h = rmsnorm(lp["ln2"]["scale"], x)
    up = _mm("td,df->tf", h, f32(lp["wi"]), precision)
    gate = jax.nn.silu(_mm("td,df->tf", h, f32(lp["wg"]), precision))
    return x + _mm("tf,fd->td", up * gate, f32(lp["wd"]), precision)


def forward(params: Dict, tokens, m: Dict, precision: str = "f32"):
    """A whole stacked tree on one sequence: tokens [T] -> logits [T, V]
    (the tests' form; the benchmark walks a layer at a time)."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for l in range(m["num_hidden_layers"]):
        x = layer(jax.tree_util.tree_map(lambda p: p[l], params["blocks"]),
                  x, m, precision)
    return head(params["embed"], params["final_norm"]["scale"], x,
                precision)
