"""Plain reference of a decoder of LATENT-attention layers with
group-routed experts (GigaChat3.1-702B-A36B, `model_type: deepseek_v3`, as
`benchmark/configs/gigachat3.1-702b-a36b-serve.json` states it).
jax.numpy in float32 at matmul precision "highest"; the EXPANDED form
only: every head's keys and values are made from the latents and every
query meets every key its causal mask allows; no cache, no absorbed
form, no batching, no sorting, no grouped product: EVERY held expert runs
on every token, its result times the token's weight for it (zero unless
chosen).  Nothing imported from the program.

Layer l on one sequence x [T, D]; H heads, eps 1e-6 (`rmsnorm`):

    u = rmsnorm(x)
    c_q = rmsnorm(u W_qa) [q_lora_rank]; q = c_q W_qb [H, nope + rope]
    (c', k') = u W_kva [kv_lora_rank + rope]; c = rmsnorm(c'); r = rope(k')
    q_i = (q_i^n, rope(q_i^r));  (k_i^n, v_i) = c W_kvb[i] [nope + v]
    k_i = (k_i^n, r): the ONE rotated key is every head's
    a_i = softmax(s q_i k_i^T + causal mask), s = (nope + rope)^-1/2 m^2,
        m = 0.1 mscale_all_dim ln(factor) + 1 (`softmax_scale`)
    x = x + concat_i(a_i v_i) W_o
    h2 = rmsnorm(x)
    dense (l < first_k_dense_replace):  x + Wd (silu(Wg h2) * Wi h2)
    sparse: sc = sigmoid(h2 W_r) over all router_width experts; choice
        scores sc' = sc + bias; the experts are n_group groups in order,
        a group scores the sum of its 2 largest sc', the topk_group best
        groups are kept and among their experts the num_experts_per_tok
        largest sc' chosen, T; w_e = routed_scaling_factor sc_e /
        (sum_T sc + 1e-20) (sc without the bias); x + sum_{e in T, held}
        w_e E_e(h2) + E_shared(h2)
    final norm; head TIED to the embedding (`reduced`)

Departures from the published code, each ASSUMED in the configuration's
file: `rope` turns the 64 dims as INTERLEAVED pairs (0,1),(2,3),.. where
the published code splits them in halves (the same up to a fixed
permutation of those dims), all 32 at YaRN's frequencies, cos and sin
times mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1; the
experts of a dropped group get a choice score of -inf where the
published code fills 0.0; the head is tied; the multi-token-prediction
block is not run; only `experts_held` of the experts add to the result.

`precision` "fp8" is the control: both operands of every matrix product
rounded to float8_e4m3, otherwise the same arithmetic.  The keyword
switches of `attention` and `mlp` leave one term out each: what the
rehearsals and the CPU tests break.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer import _mm, head, rmsnorm  # noqa: F401

Q_BLOCK = 512


def yarn_freqs(m: Dict) -> np.ndarray:
    """The angle a position of each of the rope_dim / 2 pairs, float64."""
    n = m["qk_rope_head_dim"] // 2
    rs, theta = m["rope_scaling"], float(m["rope_theta"])
    f = theta ** (-np.arange(n, dtype=np.float64) / n)
    orig = rs["original_max_position_embeddings"]

    def pair(beta):          # the pair that turns `beta` times over `orig`
        return n * math.log(orig / (2 * math.pi * beta)) / math.log(theta)

    lo = max(math.floor(pair(rs["beta_fast"])), 0)
    hi = min(math.ceil(pair(rs["beta_slow"])), 2 * n - 1)
    r = np.clip((np.arange(n) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / rs["factor"]) * r + f * (1.0 - r)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m: Dict, mscale: bool = True) -> float:
    rs = m["rope_scaling"]
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if mscale and rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, m: Dict):
    """x [T, H, rope_dim]: all of it rotates, as interleaved pairs."""
    T = x.shape[0]
    rs = m["rope_scaling"]
    scale = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_freqs(m), jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(lp: Dict, x, m: Dict, precision: str = "f32",
              mscale: bool = True, rope_score: bool = True,
              kv_norm: bool = True):
    """The attention half of a layer on one sequence x [T, D].  `mscale`
    False drops m^2 from the scale, `rope_score` False the shared key's
    part of every score, `kv_norm` False the latent's norm."""
    T = x.shape[0]
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    f32 = lambda w: w.astype(jnp.float32)
    u = rmsnorm(lp["ln1"]["scale"], x)
    cq = rmsnorm(lp["q_norm"]["scale"],
                 _mm("td,dr->tr", u, f32(lp["wq_a"]), precision))
    q = _mm("tr,rhk->thk", cq, f32(lp["wq_b"]), precision)
    kva = _mm("td,dr->tr", u, f32(lp["wkv_a"]), precision)
    c = kva[:, :R]
    if kv_norm:
        c = rmsnorm(lp["kv_norm"]["scale"], c)
    r = rope(kva[:, None, R:], m)[:, 0]                        # [T, dr]
    qn, qr = q[..., :dn], rope(q[..., dn:], m)
    if not rope_score:
        qr = jnp.zeros_like(qr)
    s = softmax_scale(m, mscale)
    qb = min(Q_BLOCK, T)
    j = jnp.arange(T)

    def one_head(args):
        qn_i, qr_i, w_i = args           # [T, dn], [T, dr], [R, dn + dv]
        kv = _mm("tr,rk->tk", c, w_i, precision)
        kn, v = kv[:, :dn], kv[:, dn:]

        def block(a):
            qn_b, qr_b, start = a
            i = start + jnp.arange(qb)
            sc = (_mm("td,sd->ts", qn_b, kn, precision)
                  + _mm("td,sd->ts", qr_b, r, precision)) * s
            p = jax.nn.softmax(
                jnp.where(j[None, :] <= i[:, None], sc, -1e30), axis=-1)
            return _mm("ts,sd->td", p, v, precision)

        return jax.lax.map(block, (qn_i.reshape(T // qb, qb, dn),
                                   qr_i.reshape(T // qb, qb, dr),
                                   jnp.arange(0, T, qb))).reshape(T, -1)

    o = jax.lax.map(one_head, (qn.transpose(1, 0, 2), qr.transpose(1, 0, 2),
                               f32(lp["wkv_b"]).transpose(1, 0, 2)))
    return x + _mm("htk,hkd->td", o, f32(lp["wo"]), precision)


def swiglu(p: Dict, h, precision: str = "f32"):
    f32 = lambda w: w.astype(jnp.float32)
    up = _mm("td,df->tf", h, f32(p["wi"]), precision)
    gate = jax.nn.silu(_mm("td,df->tf", h, f32(p["wg"]), precision))
    return _mm("tf,fd->td", up * gate, f32(p["wd"]), precision)


def routing(router, bias, h2, m: Dict, precision: str = "f32",
            groups: bool = True, scale: Optional[float] = None,
            bias_in_choice: bool = True, bias_in_weights: bool = False):
    """h2 [T, D] -> weights [T, router_width], zero but for each token's
    chosen experts.  `groups` False chooses among all experts, `scale`
    replaces the routed scale, `bias_in_choice` False chooses by the
    plain scores, `bias_in_weights` weighs by the biased ones."""
    k = m["num_experts_per_tok"]
    sc = jax.nn.sigmoid(
        _mm("td,de->te", h2, router.astype(jnp.float32), precision))
    T, E = sc.shape
    choice = sc + bias.astype(jnp.float32) if bias_in_choice else sc
    if groups:
        G = m["n_group"]
        best2 = jax.lax.top_k(choice.reshape(T, G, E // G), 2)[0]
        kept = jax.lax.top_k(jnp.sum(best2, axis=-1), m["topk_group"])[1]
        keep = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, E // G, axis=1), choice,
                           -jnp.inf)
    idx = jax.lax.top_k(choice, k)[1]
    top = jnp.take_along_axis(
        sc + bias if bias_in_weights else sc, idx, axis=-1)
    if scale is None:
        scale = m["routed_scaling_factor"]
    w = scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(sc).at[jnp.arange(T)[:, None], idx].set(w)


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
        block: int = 4, **routing_kw):
    """The MLP half of layer l, residual included.  `lp["experts"]` is a
    function (first, n) -> that block of ALL the layer's experts, asked a
    block at a time, or the stacked experts `held` (default: the
    configuration's `experts_held`), the first of them expert held[0].
    `shared` False leaves the shared expert out; `routing_kw` go to
    `routing`."""
    h2 = rmsnorm(lp["ln2"]["scale"], x)
    if l < m["first_k_dense_replace"]:
        return x + swiglu(lp, h2, precision)
    weights = routing(lp["router"], lp["router_bias"], h2, m, precision,
                      **routing_kw)
    lo, hi = held or tuple(m["experts_held"])
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
            e = slice(b - lo, min(b + block, hi) - lo)
            out = out + expert_block(
                {n: w[e] for n, w in lp["experts"].items()}, h2,
                weights[:, b:min(b + block, hi)], precision)
    if shared:
        out = out + swiglu(lp["shared"], h2, precision)
    return x + out


def layer(lp: Dict, x, m: Dict, l: int, precision: str = "f32",
          held: Optional[Tuple[int, int]] = None):
    """One whole layer on one sequence x [T, D]."""
    return mlp(lp, attention(lp, x, m, precision), m, l, precision, held)


def forward(layers, embed, final_scale, tokens, m: Dict,
            precision: str = "f32"):
    """`layers`: a list of unstacked layer trees.  tokens [T] -> logits
    [T, V]; T a multiple of `Q_BLOCK`, or under it (the tests' form; the
    benchmark walks a layer at a time)."""
    x = embed.astype(jnp.float32)[tokens]
    for l, lp in enumerate(layers):
        x = layer(lp, x, m, l, precision)
    return head(embed, final_scale, x, precision)
