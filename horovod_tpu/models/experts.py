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
`routed_scale`; the weight goes on the expert's OUTPUT.  With
`cfg.route_groups` the experts are that many groups in order, a group
scores the sum of its two best choice scores, and a token chooses among
the experts of its `route_groups_kept` best groups only (`_kept_groups`);
with none, `route` is what it was, bit for bit.

Product: the (token, expert) pairs are sorted by expert and each of an
expert's three matrices meets its own rows in ONE grouped product
(`_grouped`: the Pallas grouped matmul `megablox.gmm`, whose grid walks
the (expert, row tile) pairs that exist and so reads an expert's
weights only if a token chose it, where they lie: no gathered copy of
the experts, no product over experts nobody chose).  Operations are
those of the routed pairs, up to a row tile an expert hit.  Decode and
prefill take the same path; only the row tile differs (`_row_tile`).

`expert_layer` also counts, for whoever watches the routing
(`ROUTED`): the distinct experts held here that a token chose, the most
tokens one expert took, and the pairs that lay in a held expert's group
(every pair where all experts are held; a share's worth where a share is,
as a served GigaChat layer holds 16 of 256).  A pass longer than
`_pass_tokens` (a 16384-token prompt at a hidden width of 7168: every
pair has a row, here or not, 1.9 GB of them) goes in passes of tokens,
one after another, under the same counts.

Training (`expert_layer_train`, from `models/pattern.py` under
`make_train_step`) is the same layer under `jax.grad`: `gmm` is a
`custom_vjp` whose backward is a grouped product against the transposed
weights for the rows and `tgmm` for the weights, so gradients reach the
held experts' three stacks (an expert no token chose gets exact zeros),
the router through the weights, and the tokens.  The kernels visit the
row tiles a group reaches and write no other row.  What lies between
them, in the SORTED rows, follows the pairs as they do (`_worked`): the
rows are cut into chunks of `_CHUNK_TILES` row tiles, and the gather of
the tokens' rows into the sorted order (`_spread`), `up * silu(gate)`
and its gradient (`_gated`), the sum of the two row gradients
(`_twice`) and the gather of the output's gradient (`_collect`'s
backward) pass over the chunks up to the last that holds a pair here,
masked inside it; the chunks behind it are zeros, written once, that
nothing reads or computes.  Dropless as before: no capacity, no bound
on the pairs, no other path; with every pair here every chunk is
worked, and the sorted rows of one chunk or less (any small model) are
one masked pass with no loop.  The two gathers back into the TOKENS'
order stay whole (`_collect` forward, `_spread` backward): the pairs
that lie nowhere are spread among a token's others there, so no chunk
of them is empty until the exchange hands a chip its own pairs only
(ROADMAP R1).  The layer's OWN experts are handed to the kernel
(`stack[j]`, cast to the compute dtype: a copy of 8 experts, where the
float32 master weights need a cast anyway), so that `tgmm` writes a
gradient of one layer's experts and not of every layer's.  It counts
`TRAINED`: `ROUTED`, the pairs that lay in a group here, and the sorted
rows `_worked` passed over (whole chunks: how tight they are).
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
ROUTED = ("experts_hit", "expert_load_max", "pairs_here")
#: what a TRAINED expert layer counts a step
TRAINED = ROUTED + ("rows_worked",)


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
    if bias is None and not cfg.route_groups:
        top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
    else:
        choice = scores if bias is None \
            else scores + jax.lax.stop_gradient(bias)
        if cfg.route_groups:
            choice = _kept_groups(choice, cfg)
        _, idx = jax.lax.top_k(choice, cfg.experts_per_token)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    scaled = cfg.routed_scale * top
    den = jnp.sum(top, axis=-1, keepdims=True)
    if cfg.route_eps:
        den = den + cfg.route_eps
    return idx.astype(jnp.int32), scaled / den


def _kept_groups(choice, cfg):
    """Choice scores [N, n_experts] with every expert outside a token's
    `route_groups_kept` best groups at -inf: the experts are
    `route_groups` groups of equal size in order, and a group scores the
    sum of its two largest choice scores."""
    N, E = choice.shape
    G = cfg.route_groups
    best2, _ = jax.lax.top_k(choice.reshape(N, G, E // G), 2)
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg.route_groups_kept)
    keep = jnp.zeros((N, G), bool).at[jnp.arange(N)[:, None], kept].set(True)
    return jnp.where(jnp.repeat(keep, E // G, axis=1), choice, -jnp.inf)


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
    with no rows costs nothing.  The contraction goes in one tile up to
    a width of 2048; a wider one (a hidden width of 7168: 29 MB of weight
    tiles, which the chip's VMEM does not hold) in `_tile`'s."""
    P, K = x.shape
    L, held, _, N = stack.shape
    tn = 1024 if N > 1024 and N % 1024 == 0 else N
    return gmm(x, stack.reshape(L * held, K, N),
               jnp.pad(sizes, (j * held, (L - 1 - j) * held)),
               preferred_element_type=out_dtype,
               tiling=(_row_tile(P), K if K <= 2048 else _tile(K), tn),
               interpret=_interpret())


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


#: Bytes the sorted rows of one pass may hold in the compute dtype
#: (`expert_layer`): 8192 tokens at a hidden width of 2048 and 8 experts a
#: token, 2048 tokens at 7168.
_PASS_BYTES = 2 ** 28


def _pass_tokens(h, cfg) -> int:
    """Tokens one pass of `expert_layer` takes: the largest power of two
    whose pairs' rows fit `_PASS_BYTES`."""
    row = cfg.experts_per_token * h.shape[1] * h.dtype.itemsize
    return 1 << max((_PASS_BYTES // row).bit_length() - 1, 0)


def _expert_pass(mp: Dict, stack: Dict, j: int, h, cfg, live):
    """`expert_layer` for the tokens of one pass: (out [N, D] float32,
    sizes [held] int32, the pairs that lay in each held expert's group)."""
    dt = cfg.compute_dtype
    N, k = h.shape[0], cfg.experts_per_token
    with jax.named_scope("hvd.moe.route"):
        idx, w = route(mp["router"], h, cfg, mp.get("router_bias"))
        here, order, sizes = _sort_pairs(idx, cfg, live)
    with jax.named_scope("hvd.moe.experts"):
        P = N * k
        pad = -P % _row_tile(P)
        xs = h[jnp.pad(order // k, (0, pad))]        # [P + pad, D]
        f32 = jnp.float32
        up = _grouped(xs, stack["wi"].astype(dt), j, sizes, f32)
        gate = jax.nn.silu(
            _grouped(xs, stack["wg"].astype(dt), j, sizes, f32))
        y = _grouped((up * gate).astype(dt), stack["wd"].astype(dt), j,
                     sizes, dt)
        # rows past the pairs that lie in a group were never written
        y = jnp.where((jnp.arange(P + pad) < jnp.sum(sizes))[:, None], y, 0)
        # back to the tokens' order, each pair times its weight
        out = jnp.einsum(
            "nkd,nk->nd",
            y[jnp.argsort(order)].reshape(N, k, -1).astype(f32),
            jnp.where(here, w, 0.0))
    if "shared" in mp:
        out = out + swiglu(mp["shared"], h, dt)
    return out, sizes


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
    `out` hold the shared expert's part alone.  More tokens than
    `_pass_tokens` (a long prompt of a wide model: every pair has a row,
    here or not, until an exchange hands a chip its own) go in passes of
    that many, one after another, the counts over all of them."""
    N, D = h.shape
    step = _pass_tokens(h, cfg)
    if N <= step:
        out, sizes = _expert_pass(mp, stack, j, h, cfg, live)
    else:
        pad = -N % step
        live = jnp.ones((N,), bool) if live is None else live

        def one(a):     # kept in the dtype the caller adds it in
            out, sizes = _expert_pass(mp, stack, j, a[0], cfg, a[1])
            return out.astype(h.dtype), sizes

        out, sizes = jax.lax.map(
            one, (jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, step, D),
                  jnp.pad(live, (0, pad)).reshape(-1, step)))
        out = out.reshape(-1, D)[:N].astype(jnp.float32)
        sizes = jnp.sum(sizes, axis=0)
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes), jnp.sum(sizes)])
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


#: Row tiles (`_train_row_tile`) in a chunk of the sorted rows (`_worked`):
#: 8192 rows at the benchmark's 131072 pairs a layer.  One sweep on the
#: v5e at that shape (PERF.md 6, PR 41).
_CHUNK_TILES = 16


def _chunk_rows(rows: int) -> int:
    return _CHUNK_TILES * _train_row_tile(rows)


def _rows_worked(total, rows: int):
    """The sorted rows `_worked` passes over, of `rows`: the chunks up to
    the last that holds one of the `total` pairs here, whole."""
    step = _chunk_rows(rows)
    if rows <= step:
        return jnp.asarray(rows, jnp.int32)
    return (total + step - 1) // step * step


def _worked(fn, total, *xs):
    """`fn(*xs)` for a `fn` that works ROW BY ROW on arrays of the sorted
    pairs' rows (`xs` [P, ...] each; an array or a tuple of them back,
    [P, ...] each), over the rows that hold a pair: the rows are cut into
    chunks of `_chunk_rows`, a loop of dynamic length passes over the
    chunks up to the last that starts below `total`, writing into zeros,
    and rows from `total` on inside that last chunk are masked; the
    chunks behind it are read and computed by nobody.  ONE copy of `fn`
    in the program whatever P is.  P of one chunk or less is one masked
    pass and no loop; a longer P is whole chunks (`expert_layer_train`
    pads the pairs to them).  The loop has no reverse rule: for use inside a
    `custom_vjp`'s two functions.  A whole array that `fn` closes over
    goes through `_held` first."""
    P = xs[0].shape[0]
    step = _chunk_rows(P)
    tree_map = jax.tree_util.tree_map

    def masked(start, out):
        return tree_map(lambda o: jnp.where(
            (start + jnp.arange(o.shape[0]) < total).reshape(
                (-1,) + (1,) * (o.ndim - 1)), o, 0), out)

    if P <= step:
        return masked(0, fn(*xs))

    def body(c, out):
        start = c * step
        part = masked(start, fn(*(
            jax.lax.dynamic_slice_in_dim(x, start, step) for x in xs)))
        return tree_map(
            lambda o, p: jax.lax.dynamic_update_slice_in_dim(o, p, start, 0),
            out, part)

    zeros = tree_map(
        lambda a: jnp.zeros((P,) + a.shape[1:], a.dtype),
        jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
            (step,) + x.shape[1:], x.dtype) for x in xs)))
    return jax.lax.fori_loop(0, _rows_worked(total, P) // step, body, zeros)


def _held(x):
    """x as an array of its own: the compiler moves what makes a loop's
    operand INTO the loop where it can (the whole [P, D] gradient made
    again in every chunk: 1.7 ms a chunk on the v5e, PERF.md 6, PR 41)."""
    return jax.lax.optimization_barrier(x)


def _gated_rows(up, gate):
    return (up * jax.nn.silu(gate.astype(jnp.float32))).astype(up.dtype)


@jax.custom_vjp
def _gated(up, gate, total):
    """`up * silu(gate)`, [R, F], and its gradients, over the rows that
    hold a pair."""
    return _worked(_gated_rows, total, up, gate)


def _gated_fwd(up, gate, total):
    return _gated(up, gate, total), (up, gate, total)


def _gated_bwd(res, g):
    up, gate, total = res
    return _worked(lambda g, *xs: jax.vjp(_gated_rows, *xs)[1](g), total,
                   g, up, gate) + (None,)


_gated.defvjp(_gated_fwd, _gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_product(x, w, sizes, out_dtype):
    """x [P, K], rows sorted by group, times w [groups, K, N] group by
    group (`sizes` [groups] rows each): [P, N] in `out_dtype`.  As
    `megablox.gmm` with its backward (the rows' gradient a grouped
    product against the transposed weights, the weights' `tgmm`, which
    gives a group of no rows exact zeros), each of the three products at
    tiles of its own shape.  Rows in no group are neither read nor
    written, forward or backward: what comes out of here goes through
    `_worked` before anything reads every row of it.  (`tgmm` takes its
    rows transposed and transposes them back: the compiler drops both.)"""
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
def _spread(h, order, inv, total):
    """Tokens' rows h [N, D] to the sorted pairs: [R, D] for `order` [R]
    (the pairs by expert, padded to whole chunks), row r the token of
    pair `order[r]` where r < `total`, the pairs that lie in a group
    here, and zeros behind (`_worked`: the gather stops at the last chunk
    that holds a pair).  `inv` [P] is the inverse permutation: the
    gradient is a GATHER by it, over every pair since the pairs that lie
    nowhere are spread among a token's others, and a sum over a token's k
    pairs: the scatter-add XLA derives took three times as long on the
    v5e (PERF.md, PR 37)."""
    k, h = inv.shape[0] // h.shape[0], _held(h)
    return _worked(lambda o: h[o // k], total, order)


def _spread_fwd(h, order, inv, total):
    return _spread(h, order, inv, total), (inv, h.shape[0])


def _spread_bwd(res, g):
    inv, n = res
    return (g[inv].reshape(n, -1, g.shape[-1]).astype(jnp.float32)
            .sum(axis=1).astype(g.dtype), None, None, None)


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _twice(x, total):
    """x [R, D] for two readers, so that their two gradients are summed
    over the rows that hold a pair and nowhere else."""
    return x, x


def _twice_fwd(x, total):
    return (x, x), total


def _twice_bwd(total, g):
    return _worked(jnp.add, total, *g), None


_twice.defvjp(_twice_fwd, _twice_bwd)


@jax.custom_vjp
def _collect(y, order, inv, total):
    """Sorted pairs' rows y [R, D] back to the pairs' own order, [P, D]:
    a gather by `inv` over every pair (rows in no group come as the
    kernel left them: the caller masks by `here`).  Its gradient is the
    gather by `order`, which gives SORTED rows and stops where they
    do."""
    return y[inv]


def _collect_fwd(y, order, inv, total):
    return y[inv], (order, total)


def _collect_bwd(res, g):
    order, total = res
    g = _held(g)
    return _worked(lambda o: g[o], total, order), None, None, None


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
        # whole row tiles for the kernels, whole chunks for `_worked`
        step = _chunk_rows(P)
        rows = P + (-P % (step if P > step else _train_row_tile(P)))
        total = jnp.sum(sizes)
        inv = jnp.argsort(order)
        order = jnp.pad(order, (0, rows - P))
        # sorted rows: zeros from `total` on in all that `_worked` makes
        xa, xb = _twice(_spread(h, order, inv, total), total)
        up = grouped_product(xa, experts["wi"].astype(dt), sizes, dt)
        gate = grouped_product(xb, experts["wg"].astype(dt), sizes, dt)
        mid = _gated(up, gate, total)
        y = grouped_product(mid, experts["wd"].astype(dt), sizes, dt)
        # back to the tokens' order, each pair times its weight; a pair
        # whose expert is elsewhere reads a row no kernel wrote
        y = jnp.where(here[..., None],
                      _collect(y, order, inv, total).reshape(N, k, -1), 0)
        out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), w)
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes), total,
                        _rows_worked(total, rows)])    # `TRAINED`'s order
    return out, counts.astype(jnp.int32)


__all__ = ["ROUTED", "TRAINED", "expert_layer", "expert_layer_train",
           "grouped_product", "no_counts", "route", "sparse_layers",
           "swiglu"]
