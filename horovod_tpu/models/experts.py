"""Routed experts of a patterned model, served and trained: top-k of
`n_experts` gated SwiGLU experts a token, beside one shared expert where
the model has one, no capacity and no dropped token
(`TransformerConfig`, "a layer PATTERN").

The layer is told which experts it holds (`cfg.held`, a range), routes
over ALL `n_experts` and computes the part of the result its own experts
give, plus the shared expert, which every holder computes alike.  With
every expert held that is the whole layer; there is no exchange here
and nothing stands in for one (expert parallelism would add the
all-to-all around `expert_layer`, ROADMAP R2).

Routing: `s = sigmoid(h W_r)` over all experts, the `experts_per_token`
largest chosen, by `s` or, where the layer carries a `router_bias`, by
`s + bias` (the bias takes part in the CHOICE only: the weights are of
`s`, and nothing differentiates through a choice), their scores
renormalised to sum 1 (over `sum + cfg.route_eps`) and times
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

Training (`expert_layer_train`, from `models/pattern.py` under
`make_train_step`) is the same layer under `jax.grad`: `gmm` is a
`custom_vjp` whose backward is a grouped product against the transposed
weights for the rows and `tgmm` for the weights, so gradients reach the
held experts' three stacks (an expert no token chose gets exact zeros),
the router through the weights, and the tokens.  Rows of the sorted
pairs that lie in no group are never written by either kernel: they are
masked going in and coming out, so that nothing unwritten reaches a
sum, forward or backward.  The layer's OWN experts are handed to the
kernel there (`stack[j]`, cast to the compute dtype: a copy of 8
experts, where the float32 master weights need a cast anyway), so that
`tgmm` writes a gradient of one layer's experts and not of every
layer's.  It counts `TRAINED`: `ROUTED` and the pairs that lay in a
group here.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import (
    gmm as _gmm_kernel, tgmm as _tgmm_kernel)

from ..ops.pallas_kernels import _interpret

#: what an expert layer counts a pass, in this order
ROUTED = ("experts_hit", "expert_load_max")
#: what a TRAINED expert layer counts a step
TRAINED = ROUTED + ("pairs_here",)


def sparse_layers(cfg) -> int:
    return cfg.layer_mlp.count("experts")


def no_counts(cfg):
    """[sparse layers, len(ROUTED)] int32 zeros: `ROUTED` a layer."""
    return jnp.zeros((sparse_layers(cfg), len(ROUTED)), jnp.int32)


def route(router, h, cfg, bias=None) -> Tuple[jax.Array, jax.Array]:
    """h [N, D] -> (experts [N, k] int32, weights [N, k] float32): each
    token's `experts_per_token` experts of all `n_experts` and the weight
    of each one's output.  `bias` [n_experts] moves the choice and not
    the weights."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h, router.astype(h.dtype),
        preferred_element_type=jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
    else:
        _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                               cfg.experts_per_token)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    scaled = cfg.routed_scale * top
    den = jnp.sum(top, axis=-1, keepdims=True)
    if cfg.route_eps:
        den = den + cfg.route_eps
    return idx.astype(jnp.int32), scaled / den


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


def _sort_pairs(idx, cfg, live):
    """The (token, expert) pairs `idx` [N, k] by expert held here:
    (here [N, k] bool, order [N k], sizes [held] int32).  A pair whose
    expert is not here, or whose token is nobody's, sorts behind the
    others and lies in no group."""
    lo, hi = cfg.held
    held = hi - lo
    here = (idx >= lo) & (idx < hi)
    if live is not None:
        here &= live[:, None]
    flat = jnp.where(here, idx - lo, held).reshape(-1)
    order = jnp.argsort(flat)                    # stable: by expert
    sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    return here, order, sizes


def expert_layer(mp: Dict, stack: Dict, j: int, h, cfg,
                 live: Optional[jax.Array] = None):
    """The experts' part of sparse layer `j` for tokens h [N, D] (normed,
    in the compute dtype): (out [N, D] float32, counts [len(ROUTED)]
    int32).  `mp` is the layer's own `router` [D, n_experts] (with
    `router_bias` [n_experts] where the model chooses with one) and,
    with `cfg.shared_ff`, `shared`; `stack` {wi, wg [layers, held, D, F],
    wd [layers, held, F, D]} the experts held here of EVERY sparse layer
    (`_grouped` has why).  `live` [N] bool marks the tokens that are
    anybody's (an idle row of a served batch is nobody's): the others
    are routed nowhere, cost nothing and count nothing; their rows of
    `out` hold the shared expert's part alone."""
    dt = cfg.compute_dtype
    N, k = h.shape[0], cfg.experts_per_token
    idx, w = route(mp["router"], h, cfg, mp.get("router_bias"))
    here, order, sizes = _sort_pairs(idx, cfg, live)
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


# -- trained ---------------------------------------------------------------

def _tile(n: int) -> int:
    """A tile of a matrix dimension: all of it up to 1024, else its
    largest divisor that is a multiple of 128 and at most 1024 (2048 ->
    1024, 1792 -> 896), so that no tile hangs over the edge."""
    if n <= 1024:
        return n
    return max((t for t in range(128, 1025, 128) if n % t == 0), default=n)


def _train_row_tile(pairs: int) -> int:
    return 512 if pairs >= 4096 else 128


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_product(x, w, sizes, out_dtype):
    """x [P, K], rows sorted by group, times w [groups, K, N] group by
    group (`sizes` [groups] rows each): [P, N] in `out_dtype`.  As
    `megablox.gmm` with its backward (the rows' gradient a grouped
    product against the transposed weights, the weights' `tgmm`, which
    gives a group of no rows exact zeros), each of the three products at
    tiles of its own shape.  Rows in no group are not written, forward
    or backward: `expert_layer_train` masks them."""
    P, K = x.shape
    return _gmm_kernel(x, w, sizes, out_dtype,
                       (_train_row_tile(P), _tile(K), _tile(w.shape[2])),
                       interpret=_interpret())


def _grouped_fwd(x, w, sizes, out_dtype):
    return grouped_product(x, w, sizes, out_dtype), (x, w, sizes)


def _grouped_bwd(out_dtype, res, g):
    x, w, sizes = res
    (P, K), N = x.shape, w.shape[2]
    tm = _train_row_tile(P)
    g = g.astype(x.dtype)
    dx = _gmm_kernel(g, w, sizes, x.dtype, (tm, _tile(N), _tile(K)),
                     transpose_rhs=True, interpret=_interpret())
    dw = _tgmm_kernel(x.swapaxes(0, 1), g, sizes, w.dtype,
                      (tm, _tile(K), _tile(N)), num_actual_groups=w.shape[0],
                      interpret=_interpret())
    return dx, dw, None


grouped_product.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def _spread(h, order, inv):
    """Tokens' rows h [N, D] to the sorted pairs: [P, D], row r the token
    of pair `order[r]` (`inv` the inverse permutation).  The gradient is
    a GATHER by `inv` and a sum over a token's k pairs: the scatter-add
    XLA derives took three times as long on the v5e (PERF.md, PR 37)."""
    return h[order // (order.shape[0] // h.shape[0])]


def _spread_fwd(h, order, inv):
    return _spread(h, order, inv), (inv, h.shape[0])


def _spread_bwd(res, g):
    inv, n = res
    return (g[inv].reshape(n, -1, g.shape[-1]).astype(jnp.float32)
            .sum(axis=1).astype(g.dtype), None, None)


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _collect(y, order, inv):
    """Sorted pairs' rows y [P, D] back to the pairs' own order: a
    permutation, so its gradient is the gather by `order`."""
    return y[inv]


def _collect_fwd(y, order, inv):
    return y[inv], (order,)


def _collect_bwd(res, g):
    return g[res[0]], None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def expert_layer_train(mp: Dict, h, cfg):
    """`expert_layer` under `jax.grad`, for the layer's OWN experts,
    `mp["experts"]` {wi, wg [held, D, F], wd [held, F, D]} (float32
    master weights, cast here) beside its `router` and `router_bias`:
    (out [N, D] float32, counts [len(TRAINED)] int32).  Every token is
    somebody's; no shared expert is added (`models/pattern.py` refuses a
    trained model that has one)."""
    dt = cfg.compute_dtype
    experts = mp["experts"]
    N, k = h.shape[0], cfg.experts_per_token
    with jax.named_scope("hvd.moe.route"):
        idx, w = route(mp["router"], h, cfg, mp.get("router_bias"))
        here, order, sizes = _sort_pairs(idx, cfg, None)
    with jax.named_scope("hvd.moe.experts"):
        P = N * k
        pad = -P % _train_row_tile(P)
        # rows in no group are written by no kernel, forward or backward:
        # masked going in (which masks the gradient coming back) and out
        valid = (jnp.arange(P + pad) < jnp.sum(sizes))[:, None]
        inv = jnp.argsort(order)
        xs = jnp.where(valid, jnp.pad(_spread(h, order, inv),
                                      ((0, pad), (0, 0))), 0)
        up = grouped_product(xs, experts["wi"].astype(dt), sizes, dt)
        gate = jax.nn.silu(
            grouped_product(xs, experts["wg"].astype(dt), sizes, dt)
            .astype(jnp.float32))
        mid = jnp.where(valid, (up * gate).astype(dt), 0)
        y = jnp.where(valid, grouped_product(
            mid, experts["wd"].astype(dt), sizes, dt), 0)
        # back to the tokens' order, each pair times its weight
        out = jnp.einsum(
            "nkd,nk->nd",
            _collect(y[:P], order, inv).reshape(N, k, -1).astype(
                jnp.float32), jnp.where(here, w, 0.0))
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes), jnp.sum(sizes)])
    return out, counts.astype(jnp.int32)


__all__ = ["ROUTED", "TRAINED", "expert_layer", "expert_layer_train",
           "grouped_product", "no_counts", "route", "sparse_layers",
           "swiglu"]
