"""Routed experts for a served model: top-k of `n_experts` gated SwiGLU
experts a token beside one shared expert, no capacity and no dropped
token (`TransformerConfig`, "a layer PATTERN").

The layer is told which experts it holds (`cfg.held`, a range), routes
over ALL `n_experts` and computes the part of the result its own experts
give, plus the shared expert, which every holder computes alike.  With
every expert held that is the whole layer; there is no exchange here
and nothing stands in for one (expert parallelism would add the
all-to-all around `expert_layer`, ROADMAP R2).

Routing: `s = sigmoid(h W_r)` over all experts, the `experts_per_token`
largest chosen, their scores renormalised to sum 1 and times
`routed_scale`; the weight goes on the expert's OUTPUT.

Product: the (token, expert) pairs are sorted by expert and each of an
expert's three matrices meets its own rows in ONE grouped product
(`_grouped`: the Pallas grouped matmul `megablox.gmm`, whose grid walks
the (expert, row tile) pairs that exist and so reads an expert's
weights only if a token chose it, where they lie: no gathered copy of
the experts, no product over experts nobody chose).  Operations are
those of the routed pairs, up to a row tile an expert hit.  Decode and
prefill take the same path; only the row tile differs (`_row_tile`).

`expert_layer` also counts, for whoever watches the routing
(`ROUTED`): the distinct experts held here that a token chose, and the
most tokens one expert took.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ..ops.pallas_kernels import _interpret

#: what an expert layer counts a pass, in this order
ROUTED = ("experts_hit", "expert_load_max")


def sparse_layers(cfg) -> int:
    return cfg.layer_mlp.count("experts")


def no_counts(cfg):
    """[sparse layers, len(ROUTED)] int32 zeros: `ROUTED` a layer."""
    return jnp.zeros((sparse_layers(cfg), len(ROUTED)), jnp.int32)


def route(router, h, cfg) -> Tuple[jax.Array, jax.Array]:
    """h [N, D] -> (experts [N, k] int32, weights [N, k] float32): each
    token's `experts_per_token` experts of all `n_experts` and the weight
    of each one's output."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h, router.astype(h.dtype),
        preferred_element_type=jnp.float32))
    top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
    w = cfg.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def _row_tile(pairs: int) -> int:
    """Rows of a grouped product's tile, measured on the v5e at 256
    experts of 2048 x 512 (PERF.md, PR 34).  A decode step's 208 pairs
    over 141 experts read them at 624 GB/s with tiles of 16 rows and 657
    with 128 (the product waits for the weights either way); a prompt of
    1024 tokens does best at 128 (499 GB/s against 479 at 256), one of
    4096 at 256 (365 against 349); 512 is worse everywhere."""
    return 128 if pairs <= 16384 else 256


def _grouped(x, stack, j: int, sizes, out_dtype):
    """x [P, K], rows sorted by expert, `sizes` [held] rows an expert;
    `stack` [layers, held, K, N], every sparse layer's experts, of which
    layer `j`'s meet the rows: row r of the result is x[r] @ stack[j,
    its expert].  The stack goes to the kernel WHOLE, as layers x held
    groups of which only layer j's have rows: a slice of it handed to a
    kernel is a copy of a layer's experts (537 MB a matrix at 256
    experts of 2048 x 512, written and read again every step), a group
    with no rows costs nothing."""
    P, K = x.shape
    L, held, _, N = stack.shape
    tn = 1024 if N > 1024 and N % 1024 == 0 else N
    return gmm(x, stack.reshape(L * held, K, N),
               jnp.pad(sizes, (j * held, (L - 1 - j) * held)),
               preferred_element_type=out_dtype,
               tiling=(_row_tile(P), K, tn), interpret=_interpret())


def swiglu(p: Dict, h, dt):
    """h [N, D] through one SwiGLU (`wi`, `wg`, `wd`), float32 out."""
    up = jnp.einsum("nd,df->nf", h, p["wi"].astype(dt))
    gate = jax.nn.silu(jnp.einsum("nd,df->nf", h, p["wg"].astype(dt)))
    return jnp.einsum("nf,fd->nd", up * gate, p["wd"].astype(dt),
                      preferred_element_type=jnp.float32)


def expert_layer(mp: Dict, stack: Dict, j: int, h, cfg,
                 live: Optional[jax.Array] = None):
    """The experts' part of sparse layer `j` for tokens h [N, D] (normed,
    in the compute dtype): (out [N, D] float32, counts [len(ROUTED)]
    int32).  `mp` is the layer's own `router` [D, n_experts] and, with
    `cfg.shared_ff`, `shared`; `stack` {wi, wg [layers, held, D, F], wd
    [layers, held, F, D]} the experts held here of EVERY sparse layer
    (`_grouped` has why).  `live` [N] bool marks the tokens that are
    anybody's (an idle row of a served batch is nobody's): the others
    are routed nowhere, cost nothing and count nothing; their rows of
    `out` hold the shared expert's part alone."""
    dt = cfg.compute_dtype
    N, k = h.shape[0], cfg.experts_per_token
    lo, hi = cfg.held
    held = hi - lo
    idx, w = route(mp["router"], h, cfg)
    here = (idx >= lo) & (idx < hi)
    if live is not None:
        here &= live[:, None]
    # a pair whose expert is not here, or whose token is nobody's, sorts
    # behind the others and lies in no group
    flat = jnp.where(here, idx - lo, held).reshape(-1)
    order = jnp.argsort(flat)                    # stable: by expert
    sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    P = N * k
    pad = -P % _row_tile(P)
    xs = h[jnp.pad(order // k, (0, pad))]        # [P + pad, D]
    f32 = jnp.float32
    up = _grouped(xs, stack["wi"].astype(dt), j, sizes, f32)
    gate = jax.nn.silu(_grouped(xs, stack["wg"].astype(dt), j, sizes, f32))
    y = _grouped((up * gate).astype(dt), stack["wd"].astype(dt), j, sizes,
                 dt)
    # rows past the pairs that lie in a group were never written
    y = jnp.where((jnp.arange(P + pad) < jnp.sum(sizes))[:, None], y, 0)
    # back to the tokens' order, each pair times its weight
    out = jnp.einsum(
        "nkd,nk->nd", y[jnp.argsort(order)].reshape(N, k, -1).astype(f32),
        jnp.where(here, w, 0.0))
    if "shared" in mp:
        out = out + swiglu(mp["shared"], h, dt)
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes)])
    return out, counts.astype(jnp.int32)


__all__ = ["ROUTED", "expert_layer", "no_counts", "route", "sparse_layers",
           "swiglu"]
