"""Plain reference of a decoder whose layers follow a PATTERN
(Laguna-XS.2 as `benchmark/configs/laguna-xs2-serve.json` states it):
layers of two kinds of attention that differ in heads, window and rotary
form, a sigmoid gate a head on the attention output, and after a leading
dense SwiGLU layer, layers of routed SwiGLU experts beside a shared one.
jax.numpy in float32 at matmul precision "highest"; no cache, no
batching, no sorting, no grouped product: every query meets every key its
mask allows and EVERY expert runs on every token, its result times the
token's weight for it (zero unless chosen).  Nothing imported from the
program.

Layer l, kind t = layer_types[l], H = num_attention_heads_per_layer[l],
d = head_dim, 8 kv heads, eps 1e-6:

    h = rmsnorm(x); q = h Wq [H, d]; k = h Wk, v = h Wv [8, d]
        (no q/k norm: ASSUMED, the config names none)
    rotary on q and k by rope_parameters[t]: "default" rotates all of
        `partial_rotary_factor` x d dims at theta^(-2i/n); "yarn" as
        published (`yarn_freqs`), cos and sin times attention_factor;
        the other dims pass.  Pairs are INTERLEAVED (0,1),(2,3),.. where
        the source's code splits the head in halves: the same up to a
        fixed permutation of a head's dims (ASSUMED, as the program
        rotates)
    a = softmax(q k^T / sqrt(d) + causal mask [+ j > i - window])
    g = sigmoid(h Wg) [H]   (gate per head: ASSUMED from the sibling
        Laguna-S-2.1's `gating: per-head`; the parameter count bears it
        out); x = x + (g * (a v)) Wo
    h2 = rmsnorm(x)
    dense:   x + Wd (silu(Wg' h2) * Wi h2)
    sparse:  s = sigmoid(h2 Wr) over all num_experts (ASSUMED: sigmoid,
        the chosen renormalised), T = the num_experts_per_tok largest,
        w_e = moe_routed_scaling_factor s_e / sum_T s;
        x + sum_{e in T} w_e E_e(h2) + E_shared(h2), the weight on the
        output (moe_apply_router_weight_on_input false)
    final norm; head TIED to the embedding (`reduced`)

`precision` "fp8" is the control: both operands of every matrix product
rounded to float8_e4m3, otherwise the same arithmetic.

The experts come in blocks (`expert_block`) so that float32 copies of a
block fit beside whatever else the chip holds; `held` cuts the experts to
a range, for the test that ties a chip's share to the whole layer.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer import _mm, head, rmsnorm  # noqa: F401

Q_BLOCK = 512


def yarn_freqs(rp: Dict, d_head: int) -> np.ndarray:
    """The angle a position of each rotated pair, float64 [n]."""
    n = int(d_head * rp.get("partial_rotary_factor", 1.0)) // 2
    theta = float(rp["rope_theta"])
    f = theta ** (-2.0 * np.arange(n) / (2 * n))
    if rp.get("rope_type", "default") != "yarn":
        return f
    dims, orig = 2 * n, rp["original_max_position_embeddings"]

    def pair(beta):          # the pair that turns `beta` times over `orig`
        return dims * math.log(orig / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(rp["beta_fast"])), 0)
    hi = min(math.ceil(pair(rp["beta_slow"])), dims - 1)
    r = np.clip((np.arange(n) - lo) / (hi - lo), 0.0, 1.0)
    return (f / rp["factor"]) * r + f * (1.0 - r)


def rotary(x, rp: Dict):
    """x [T, H, d]: the first 2n dims rotate as interleaved pairs."""
    T, _, d = x.shape
    f = yarn_freqs(rp, d)
    n = len(f)
    scale = float(rp.get("attention_factor", 1.0)) \
        if rp.get("rope_type", "default") == "yarn" else 1.0
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(f, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., 0:2 * n:2], x[..., 1:2 * n:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                    axis=-1).reshape(T, x.shape[1], 2 * n)
    return jnp.concatenate([rot, x[..., 2 * n:]], axis=-1)


def attention(lp: Dict, x, m: Dict, l: int, precision: str = "f32",
              gate: bool = True, window: Optional[int] = None):
    """The attention half of layer l on one sequence x [T, D].  `gate`
    False and `window` (None: the layer's own; 0: the whole context) are
    what the rehearsals break."""
    T = x.shape[0]
    kind = m["layer_types"][l]
    H, Hkv, d = (m["num_attention_heads_per_layer"][l],
                 m["num_key_value_heads"], m["head_dim"])
    g = H // Hkv
    if window is None:
        window = m["sliding_window"] if kind == "sliding_attention" else 0
    rp = m["rope_parameters"][kind]
    f32 = lambda w: w.astype(jnp.float32)
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = rotary(_mm("td,dhk->thk", h, f32(lp["wq"]), precision), rp)
    k = rotary(_mm("td,dhk->thk", h, f32(lp["wk"]), precision), rp)
    v = _mm("td,dhk->thk", h, f32(lp["wv"]), precision)
    qb = min(Q_BLOCK, T)
    j = jnp.arange(T)

    def group(args):                     # one kv head and its g queries
        qg, kg, vg = args                # [T, g, d], [T, d], [T, d]

        def block(a):
            qs, start = a                # [qb, g, d]
            i = start + jnp.arange(qb)
            mask = j[None, :] <= i[:, None]
            if window:
                mask &= (i[:, None] - j[None, :]) < window
            s = _mm("tgd,sd->gts", qs, kg, precision) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
            return _mm("gts,sd->tgd", p, vg, precision)

        return jax.lax.map(block, (qg.reshape(T // qb, qb, g, d),
                                   jnp.arange(0, T, qb))).reshape(T, g, d)

    o = jax.lax.map(group, (q.reshape(T, Hkv, g, d).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, H, d)
    if gate:
        o = o * jax.nn.sigmoid(
            _mm("td,dh->th", h, f32(lp["w_gate"]), precision))[..., None]
    return x + _mm("thk,hkd->td", o, f32(lp["wo"]), precision)


def swiglu(p: Dict, h, precision: str = "f32"):
    f32 = lambda w: w.astype(jnp.float32)
    up = _mm("td,df->tf", h, f32(p["wi"]), precision)
    gate = jax.nn.silu(_mm("td,df->tf", h, f32(p["wg"]), precision))
    return _mm("tf,fd->td", up * gate, f32(p["wd"]), precision)


def routing(router, h2, m: Dict, precision: str = "f32",
            scale: Optional[float] = None):
    """h2 [T, D] -> (weights [T, E], zero but for each token's chosen
    experts, and margin [T]: how far the last chosen expert's router
    logit lies above the first one left out)."""
    k = m["num_experts_per_tok"]
    z = _mm("td,de->te", h2, router.astype(jnp.float32), precision)
    s = jax.nn.sigmoid(z)
    top, idx = jax.lax.top_k(s, k)
    if scale is None:
        scale = m["moe_routed_scaling_factor"]
    w = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    weights = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                   idx].set(w)
    zs = jax.lax.top_k(z, k + 1)[0]
    return weights, zs[:, k - 1] - zs[:, k]


def expert_block(block: Dict, h2, weights, precision: str = "f32"):
    """sum over the block's experts e of weights[:, e] * E_e(h2): block
    {wi, wg [n, D, F], wd [n, F, D]}, weights [T, n]."""
    f32 = lambda w: w.astype(jnp.float32)
    up = _mm("td,edf->etf", h2, f32(block["wi"]), precision)
    gate = jax.nn.silu(_mm("td,edf->etf", h2, f32(block["wg"]), precision))
    y = _mm("etf,efd->etd", up * gate, f32(block["wd"]), precision)
    return jnp.sum(y * weights.T[:, :, None], axis=0)


def mlp(lp: Dict, x, m: Dict, l: int, precision: str = "f32",
        held: Optional[Tuple[int, int]] = None, shared: bool = True,
        scale: Optional[float] = None, block: int = 16):
    """The MLP half of layer l, residual included: (x, margin [T] or
    None).  `lp["experts"]` holds all the layer's experts stacked, or is
    a function (first, n) -> that block of them, asked a block at a time.
    `held` [lo, hi): only those experts' part; `shared` False leaves the
    shared expert out; `scale` replaces the routed scale."""
    h2 = rmsnorm(lp["ln2"]["scale"], x)
    if m["mlp_layer_types"][l] == "dense":
        return x + swiglu(lp, h2, precision), None
    weights, margin = routing(lp["router"], h2, m, precision, scale)
    lo, hi = held or (0, m["num_experts"])
    if callable(lp["experts"]):
        def one(out, first):
            w = jax.lax.dynamic_slice_in_dim(weights, first, block, axis=1)
            return out + expert_block(lp["experts"](first, block), h2, w,
                                      precision), None
        out = jax.lax.scan(one, jnp.zeros_like(x),
                           jnp.arange(lo, hi, block))[0]
    else:
        out = jnp.zeros_like(x)
        for b in range(lo, hi, block):
            e = slice(b, min(b + block, hi))
            out = out + expert_block(
                {n: w[e] for n, w in lp["experts"].items()}, h2,
                weights[:, e], precision)
    if shared and "shared" in lp:
        out = out + swiglu(lp["shared"], h2, precision)
    return x + out, margin


def layer(lp: Dict, x, m: Dict, l: int, precision: str = "f32"):
    """One whole layer on one sequence: (x [T, D], margin or None)."""
    return mlp(lp, attention(lp, x, m, l, precision), m, l, precision)


def forward(layers, embed, final_scale, tokens, m: Dict,
            precision: str = "f32"):
    """`layers`: a list of unstacked layer trees.  tokens [T] -> (logits
    [T, V], margins [sparse layers, T]); T a multiple of `Q_BLOCK`, or
    under it (the tests' form; the benchmark walks a layer at a time)."""
    x = embed.astype(jnp.float32)[tokens]
    margins = []
    for l, lp in enumerate(layers):
        x, margin = layer(lp, x, m, l, precision)
        if margin is not None:
            margins.append(margin)
    return head(embed, final_scale, x, precision), jnp.stack(margins)
