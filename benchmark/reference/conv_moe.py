"""Plain reference of a decoder whose layers mix tokens by a gated short
convolution or by softmax attention and whose FFNs are dense or routed
experts chosen with a bias (LFM2-8B-A1B as
`benchmark/configs/lfm2-8b-a1b-train.json` states it), with its loss,
gradients and AdamW step.  jax.numpy in float32 at matmul precision
"highest"; no kernel, no sorting, no grouped product, nothing imported
from the program: the convolution is three shifted products, attention a
masked softmax in query blocks, the experts a loop over the experts held
with a mask, the choice its own top-k.

Layer l of the run (source layer `first_source_layer + l`), eps 1e-6:

    h = x + Mixer(rmsnorm(x));  y = h + FFN(rmsnorm(h))
    conv:  (b, c, u) = split3(z W_in); g = b * u;
           v_t = sum_{j<taps} w[:, j] g_{t-(taps-1)+j}, g zero before the
           sequence's start; out (c * v) W_out; no bias
    full_attention: GQA, q and k RMS-normed a head with a learned scale
           (ASSUMED from the published lfm2 implementation), rotary on the
           whole head at rope_theta, INTERLEAVED pairs (ASSUMED, as the
           program rotates; the source rotates halves), causal, 1/sqrt(d)
    dense: SwiGLU of intermediate_size
    experts: s = sigmoid(z W_r) over all router_width experts; the
           num_experts_per_tok largest of s + bias are chosen (the bias
           in the CHOICE only); weight s_e / (sum of the chosen s + 1e-6)
           times routed_scaling_factor on the expert's OUTPUT; only the
           experts `experts_held` add anything; no shared expert
    final norm; head TIED to the embedding; mean cross-entropy

`precision` "fp8" is the control: both operands of every matrix product
rounded to float8_e4m3, otherwise the same arithmetic.  The named
arguments `routed_scale`, `bias_in_weights`, `causal_taps` and `skip_expert`
are what the rehearsals break.

The tree is the stacked one the program trains (`lib/weights_conv_moe.py`
`params`), so that a gradient here and there have the same leaves.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.transformer import (  # noqa: F401
    _mm, adamw_step as _adamw_step, head, rmsnorm, rope)

Q_BLOCK = 512
ROUTE_EPS = 1e-6


def kinds(m: Dict):
    first = m.get("first_source_layer", 0)
    return [(m["layer_types"][first + l],
             "dense" if l < m["num_dense_layers"] else "experts")
            for l in range(m["num_hidden_layers"])]


def conv_mixer(lp: Dict, h, precision: str = "f32", causal_taps=True):
    """h [T, D] normed -> [T, D]."""
    T, D = h.shape
    w = lp["w_conv"].astype(jnp.float32)
    taps = w.shape[1]
    z = _mm("td,de->te", h, lp["w_in"].astype(jnp.float32), precision)
    b, c, u = z[:, :D], z[:, D:2 * D], z[:, 2 * D:]
    g = b * u
    v = jnp.zeros_like(g)
    for j in range(taps):
        back = (taps - 1 - j) if causal_taps else (taps // 2 - j)
        # g shifted `back` positions towards later tokens, zeros let in
        if back >= 0:
            shifted = jnp.concatenate(
                [jnp.zeros((back, D), g.dtype), g[:T - back]], axis=0)
        else:
            shifted = jnp.concatenate(
                [g[-back:], jnp.zeros((-back, D), g.dtype)], axis=0)
        v = v + w[:, j] * shifted
    return _mm("td,de->te", c * v, lp["w_out"].astype(jnp.float32),
               precision)


def attention_mixer(lp: Dict, h, m: Dict, precision: str = "f32"):
    """h [T, D] normed -> [T, D]: causal GQA with q/k norm."""
    T = h.shape[0]
    H, Hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    g = H // Hkv
    f32 = lambda w: w.astype(jnp.float32)
    q = _mm("td,dhk->thk", h, f32(lp["wq"]), precision)
    k = _mm("td,dhk->thk", h, f32(lp["wk"]), precision)
    v = _mm("td,dhk->thk", h, f32(lp["wv"]), precision)
    q = rope(rmsnorm(lp["q_norm"]["scale"], q), m["rope_theta"])
    k = rope(rmsnorm(lp["k_norm"]["scale"], k), m["rope_theta"])
    qb = min(Q_BLOCK, T)
    j = jnp.arange(T)

    def group(args):                     # one kv head and its g queries
        qg, kg, vg = args                # [T, g, d], [T, d], [T, d]

        @jax.checkpoint
        def block(a):
            qs, start = a                # [qb, g, d]
            i = start + jnp.arange(qb)
            mask = j[None, :] <= i[:, None]
            s = _mm("tgd,sd->gts", qs, kg, precision) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
            return _mm("gts,sd->tgd", p, vg, precision)

        return jax.lax.map(block, (qg.reshape(T // qb, qb, g, d),
                                   jnp.arange(0, T, qb))).reshape(T, g, d)

    o = jax.lax.map(group, (q.reshape(T, Hkv, g, d).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, H, d)
    return _mm("thk,hkd->td", o, f32(lp["wo"]), precision)


def swiglu(wi, wg, wd, h, precision: str = "f32"):
    f32 = lambda w: w.astype(jnp.float32)
    up = _mm("td,df->tf", h, f32(wi), precision)
    gate = jax.nn.silu(_mm("td,df->tf", h, f32(wg), precision))
    return _mm("tf,fd->td", up * gate, f32(wd), precision)


def routing(lp: Dict, h, m: Dict, precision: str = "f32",
            routed_scale: Optional[float] = None,
            bias_in_weights: bool = False):
    """h [T, D] -> weights [T, router_width], zero but for each token's
    chosen experts."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("td,de->te", h,
                           lp["router"].astype(jnp.float32), precision))
    biased = s + jax.lax.stop_gradient(lp["router_bias"])
    _, idx = jax.lax.top_k(biased, k)
    rows = jnp.arange(s.shape[0])[:, None]
    top = (biased if bias_in_weights else s)[rows, idx]
    if routed_scale is None:
        routed_scale = m["routed_scaling_factor"]
    w = routed_scale * top / (jnp.sum(top, axis=-1, keepdims=True)
                              + ROUTE_EPS)
    return jnp.zeros_like(s).at[rows, idx].set(w)


def experts(lp: Dict, h, m: Dict, precision: str = "f32", share=None,
            skip_expert: Optional[int] = None, **route):
    """The routed experts' part for h [T, D]: the experts held, one
    after another, each on every token, its result times the token's
    weight for it (zero unless chosen).  `share` [lo, hi): the numbers,
    among all the router's experts, of the experts `lp["experts"]`
    stacks (None: the configuration's `experts_held`)."""
    lo, hi = share or tuple(m.get("experts_held")
                            or (0, m["num_experts"]))
    weights = routing(lp, h, m, precision, **route)
    e = lp["experts"]

    def one(out, a):
        wi, wg, wd, w, n = a
        y = swiglu(wi, wg, wd, h, precision) * w[:, None]
        return out + (jnp.where(n == skip_expert, 0.0, y)
                      if skip_expert is not None else y), None

    return jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (e["wi"], e["wg"], e["wd"], weights[:, lo:hi].T,
         jnp.arange(lo, hi)))[0]


def layer(ap: Dict, mp: Dict, x, m: Dict, kind, precision: str = "f32",
          share=None, causal_taps: bool = True, **route):
    """One block on one sequence x [T, D]; `kind` (mixer, ffn)."""
    h = rmsnorm(ap["ln1"]["scale"], x)
    if kind[0] == "conv":
        x = x + conv_mixer(ap, h, precision, causal_taps)
    else:
        x = x + attention_mixer(ap, h, m, precision)
    h = rmsnorm(mp["ln2"]["scale"], x)
    if kind[1] == "dense":
        return x + swiglu(mp["wi"], mp["wg"], mp["wd"], h, precision)
    return x + experts(mp, h, m, precision, share, **route)


def forward(params: Dict, tokens, m: Dict, precision: str = "f32",
            **how):
    """One sequence tokens [T] -> x [T, D] before the final norm; every
    layer is rematerialised in the backward pass."""
    x = params["embed"].astype(jnp.float32)[tokens]
    seen = {}
    for kind in kinds(m):
        j, jm = seen.get(kind[0], 0), seen.get(kind[1], 0)
        seen[kind[0]], seen[kind[1]] = j + 1, jm + 1
        ap = jax.tree_util.tree_map(lambda p: p[j], params["attn"][kind[0]])
        mp = jax.tree_util.tree_map(lambda p: p[jm], params["mlp"][kind[1]])
        x = jax.checkpoint(functools.partial(
            layer, m=m, kind=kind, precision=precision, **how))(ap, mp, x)
    return x


def logits(params: Dict, tokens, m: Dict, precision: str = "f32", **how):
    """tokens [T] -> [T, V]."""
    return head(params["embed"], params["final_norm"]["scale"],
                forward(params, tokens, m, precision, **how), precision)


def sequence_loss(params: Dict, tokens, targets, m: Dict,
                  precision: str = "f32", **how):
    """Summed next-token cross-entropy of one sequence."""
    lg = logits(params, tokens, m, precision, **how)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    return jnp.sum(lse - picked)


def loss(params: Dict, tokens, targets, m: Dict, precision: str = "f32",
         **how):
    """Mean cross-entropy over rows [B, T]."""
    total = sum(sequence_loss(params, tokens[b], targets[b], m, precision,
                              **how) for b in range(tokens.shape[0]))
    return total / tokens.size


def adamw_step(params, grads, mu, nu, count, hp: Dict):
    """One plain AdamW step; the router's bias takes none."""
    new, mu, nu = _adamw_step(params, grads, mu, nu, count, hp)
    if "experts" in params["mlp"]:
        new["mlp"]["experts"]["router_bias"] = \
            params["mlp"]["experts"]["router_bias"]
    return new, mu, nu
