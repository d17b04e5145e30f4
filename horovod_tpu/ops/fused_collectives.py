"""Fused computation-collective pipeline (PAPERS.md: "Optimizing
Distributed ML Communication with Fused Computation-Collective
Operations"; chunk algebra per "Memory-efficient array redistribution
through portable collective communication").

PERF_NOTES r6-r8 end at the same wall: with full overlap-aware
bucketing, collectives are still ~49% of the simulated n=8 step because
the residual wire time is exposed INSIDE bucket boundaries — scheduling
whole-bucket collectives against other buckets' compute cannot hide the
serial encode -> transfer -> decode chain of any single bucket.  This
module attacks that intra-bucket serialization with three fusions:

(a) ``fused_matmul_reduce_scatter`` — the LAST layers' backward matmul
    fused with the FIRST bucket's reduce-scatter: the product's column
    chunks reduce-scatter while later chunks are still being produced,
    so ring steps start before the grad exists in full.
(b) ``fused_allgather_matmul`` — the ZeRO-1 param-allgather fused with
    the first forward matmul that consumes it: shard chunks gather in
    consumption order (reverse-availability bucket order IS the
    prefetch schedule) and each gathered band multiplies immediately.
(c) ``pipelined_allreduce_shard`` / ``pipelined_psum_scatter`` /
    ``pipelined_allgather_shard`` — large buckets split into
    ``fused_chunk_bytes`` chunks so WireCodec encode -> ring hop ->
    decode/accumulate software-pipelines: chunk j's codec work hides
    behind chunk j-1's in-flight transfer instead of serializing.

Chunk boundaries are ``_BLOCK``-aligned, so the cooperative codecs'
block-scale boundaries never move: the chunked quantized allgather is
BITWISE-equal to the unfused one, and the exact/cast paths are bitwise
because psum / psum_scatter / all_gather are elementwise — chunking a
buffer cannot change any element's reduction order.  (The chunked
quantized ALLREDUCE re-partitions the ring's per-rank sub-chunks, so it
agrees to wire tolerance only — same contract as bucket-order
permutation, docs/WIRE.md.)

Everything is gated on ``HOROVOD_FUSED_COLLECTIVES=1`` (`fused_enabled`)
and sized by the ``fused_chunk_bytes`` autotuner knob
(HOROVOD_FUSED_CHUNK_BYTES seed).  The matmul chunk compute can ride a
Pallas tiled kernel (HOROVOD_FUSED_PALLAS=1), with interpret-mode
fallback via `pallas_kernels._interpret()` so CPU tier-1 runs every
path.  See docs/FUSED_COLLECTIVES.md.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common import util
from ..common.exceptions import HorovodTpuError
from .pallas_kernels import _LANES, _interpret
from .wire import _BLOCK, get_codec


def fused_enabled() -> bool:
    """Whether the fused computation-collective pipeline is armed
    (HOROVOD_FUSED_COLLECTIVES=1).  Read at trace time — the program
    cache key includes it, so flipping the env forces a retrace."""
    return util.env_bool("FUSED_COLLECTIVES", False)


def fused_pallas_enabled(n_elements: int) -> bool:
    """Whether the fused matmul chunks run through the Pallas tiled
    kernel (HOROVOD_FUSED_PALLAS=1) instead of the XLA dot
    decomposition.  Mirrors `pallas_enabled`: opt-in, and tiny operands
    stay on XLA where kernel launch overhead would dominate."""
    if n_elements < _LANES * _LANES:
        return False
    return util.env_bool("FUSED_PALLAS", False)


def plan_chunks(n_elements: int, itemsize: int,
                chunk_bytes: Optional[int] = None,
                align: int = _BLOCK) -> List[Tuple[int, int]]:
    """The software-pipeline schedule: ``[(offset, length), ...]``
    covering a flat n-element buffer in ``chunk_bytes``-sized pieces
    (default: the live `fused_chunk_bytes` knob).  Every offset is a
    multiple of `align` (= the codec scale block), so chunking never
    moves a block-scale boundary and the per-chunk encodes of an
    aligned buffer are bitwise-identical to the whole-buffer encode."""
    if n_elements <= 0:
        return [(0, max(0, n_elements))]
    if chunk_bytes is None:
        from ..utils.autotune import current_fused_chunk_bytes
        chunk_bytes = current_fused_chunk_bytes()
    per = max(1, int(chunk_bytes) // max(1, int(itemsize)))
    per = max(align, (per // align) * align)
    out = []
    off = 0
    while off < n_elements:
        w = min(per, n_elements - off)
        out.append((off, w))
        off += w
    return out


# ---------------------------------------------------------------------------
# (c) chunked software-pipelined collectives
# ---------------------------------------------------------------------------

def pipelined_allreduce_shard(flat: jax.Array, axis: str,
                              average: bool = False, wire: str = "int8",
                              error_feedback: jax.Array = None,
                              chunk_bytes: Optional[int] = None):
    """Chunked quantized ring allreduce: each chunk runs its own
    encode -> n-1 ring hops -> decode/accumulate, so chunk j's codec
    work issues while chunk j-1's payload is still in flight (XLA
    schedules the independent chains concurrently).  Same signature and
    EF contract as `quantized_allreduce_shard`; results agree to wire
    tolerance (the ring's per-rank sub-chunk boundaries move with the
    chunking — exact wires should take `pipelined_grouped_allreduce`,
    which is bitwise)."""
    from .quantized import quantized_allreduce_shard

    if flat.ndim != 1:
        raise HorovodTpuError(
            f"pipelined_allreduce_shard needs a flat buffer; got shape "
            f"{flat.shape}")
    chunks = plan_chunks(flat.size, flat.dtype.itemsize,
                         chunk_bytes=chunk_bytes)
    outs, resids = [], []
    for off, w in chunks:
        seg = flat[off:off + w]
        if error_feedback is not None:
            red, err = quantized_allreduce_shard(
                seg, axis, average=average, wire=wire,
                error_feedback=error_feedback[off:off + w])
            outs.append(red)
            resids.append(err)
        else:
            outs.append(quantized_allreduce_shard(
                seg, axis, average=average, wire=wire))
    out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    if error_feedback is not None:
        resid = (jnp.concatenate(resids) if len(resids) > 1
                 else resids[0])
        return out, resid
    return out


def pipelined_grouped_allreduce(tensors, op=None, axis_name: str = None,
                                chunk_bytes: Optional[int] = None):
    """Chunked exact grouped allreduce: the same dtype-bucketed
    flatten/concat as `grouped_allreduce`, but each fused buffer is
    reduced in `fused_chunk_bytes` chunks so the first chunk's
    collective issues while the rest of the bucket is still being
    packed.  psum/pmean are elementwise, so this is BITWISE-equal to
    the unfused grouped collective — the fused exact path's parity
    contract."""
    from . import collectives as C

    if op is None:
        op = C.Average
    if not tensors:
        return []
    flat = [jnp.ravel(t).astype(jnp.result_type(t)) for t in tensors]
    sizes = [f.size for f in flat]
    out = [None] * len(tensors)
    by_dtype = {}
    for i, f in enumerate(flat):
        by_dtype.setdefault(f.dtype, []).append(i)
    for dt, idxs in by_dtype.items():
        buf = (jnp.concatenate([flat[i] for i in idxs])
               if len(idxs) > 1 else flat[idxs[0]])
        red_chunks = [
            C.allreduce(buf[off:off + w], op=op, axis_name=axis_name)
            for off, w in plan_chunks(buf.size, jnp.dtype(dt).itemsize,
                                      chunk_bytes=chunk_bytes)]
        red = (jnp.concatenate(red_chunks) if len(red_chunks) > 1
               else red_chunks[0])
        offset = 0
        for i in idxs:
            out[i] = red[offset:offset + sizes[i]].reshape(
                jnp.shape(tensors[i]))
            offset += sizes[i]
    return out


def pipelined_psum_scatter(flat: jax.Array, axis: str,
                           chunk_bytes: Optional[int] = None) -> jax.Array:
    """Chunked reduce-scatter of a flat buffer divisible by the axis
    size: the buffer is viewed as (n, shard) bands and shard-dim chunks
    scatter independently, so early chunks' ring steps run while later
    chunks are still being produced (the ZeRO-1 gradient path).
    Reassembled per shard it is BITWISE-equal to
    ``lax.psum_scatter(flat, axis, tiled=True)`` — the scatter sums
    elementwise and every element keeps its rank ownership."""
    n = lax.psum(1, axis)
    if flat.ndim != 1 or flat.size % n:
        raise HorovodTpuError(
            f"pipelined_psum_scatter needs a flat buffer divisible by "
            f"the axis size ({n}); got shape {flat.shape}")
    shard = flat.size // n
    band = flat.reshape(n, shard)
    outs = [
        lax.psum_scatter(band[:, off:off + w].reshape(-1), axis,
                         tiled=True)
        for off, w in plan_chunks(shard, flat.dtype.itemsize,
                                  chunk_bytes=chunk_bytes)]
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


def pipelined_allgather_shard(shard: jax.Array, axis: str,
                              wire: Optional[str] = None,
                              chunk_bytes: Optional[int] = None,
                              stacked: bool = False) -> jax.Array:
    """Chunked tiled all-gather of a flat local shard: chunks gather in
    consumption order so the first band is available while later chunks
    are in flight (the ZeRO-1 param-prefetch schedule).  Cooperative
    `wire` formats encode per chunk — offsets are _BLOCK-aligned, so
    the block scales match the whole-buffer encode and the result is
    BITWISE-equal to `quantized_allgather_shard`; exact/cast gathers
    are bitwise trivially (gathers move bytes).

    Returns the rank-major flat gather (`lax.all_gather(tiled=True)`
    layout), or the (n, size) stacked view when ``stacked=True``."""
    from .quantized import quantized_allgather_shard

    if shard.ndim != 1:
        raise HorovodTpuError(
            f"pipelined_allgather_shard needs a flat shard; got shape "
            f"{shard.shape}")
    codec = get_codec(wire)
    n = lax.psum(1, axis)
    rows = []
    for off, w in plan_chunks(shard.size, shard.dtype.itemsize,
                              chunk_bytes=chunk_bytes):
        seg = shard[off:off + w]
        if codec.cooperative:
            g = quantized_allgather_shard(seg, axis, wire=codec.name)
        else:
            g = lax.all_gather(seg, axis, tiled=True)
        rows.append(g.reshape(n, w))
    band = jnp.concatenate(rows, axis=1) if len(rows) > 1 else rows[0]
    return band if stacked else band.reshape(-1)


# ---------------------------------------------------------------------------
# Pallas tiled-matmul chunk kernel (the compute half of fusions a/b)
# ---------------------------------------------------------------------------

_MM_BLOCK = 128  # MXU-shaped tile for every matmul grid dimension


def _matmul_kernel(a_ref, b_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def pallas_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M, K) @ (K, N) through a 128x128x128-tiled Pallas kernel with
    f32 accumulation — the compute stage of the fused chunks when
    `fused_pallas_enabled`.  Interpret mode (`_interpret()`) keeps the
    kernel CI-runnable on CPU; zero padding is exact for matmul."""
    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise HorovodTpuError(
            f"pallas_matmul: inner dims disagree ({a.shape} @ {b.shape})")
    mp = -(-m // _MM_BLOCK) * _MM_BLOCK
    kp = -(-k // _MM_BLOCK) * _MM_BLOCK
    np_ = -(-n // _MM_BLOCK) * _MM_BLOCK
    at, bt = _pad2(a, mp, kp), _pad2(b, kp, np_)
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // _MM_BLOCK, np_ // _MM_BLOCK, kp // _MM_BLOCK),
        in_specs=[
            pl.BlockSpec((_MM_BLOCK, _MM_BLOCK), lambda i, j, s: (i, s)),
            pl.BlockSpec((_MM_BLOCK, _MM_BLOCK), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((_MM_BLOCK, _MM_BLOCK),
                               lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        interpret=_interpret(),
    )(at, bt)
    return out[:m, :n]


def _chunk_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """One fused chunk's matmul: Pallas tiles when enabled, XLA dot
    otherwise (the decomposed fallback every platform runs)."""
    if fused_pallas_enabled(a.size + b.size):
        return pallas_matmul(a, b)
    return jnp.dot(a, b, preferred_element_type=a.dtype)


# ---------------------------------------------------------------------------
# (a) backward matmul fused with the first bucket's reduce-scatter
# ---------------------------------------------------------------------------

def fused_matmul_reduce_scatter(a: jax.Array, b: jax.Array, axis: str,
                                average: bool = False,
                                chunk_bytes: Optional[int] = None
                                ) -> jax.Array:
    """``psum_scatter(a @ b)`` with the matmul still in flight: the
    output's column dim is chunked, and chunk j's reduce-scatter issues
    the moment its partial product exists — while chunk j+1's matmul
    (the rest of the backward) is still running.  This is the
    grad-weight fusion: a = activationsᵀ (M = fan-out rows, divisible
    by the axis size n), b = upstream grads (K, N columns).

    Returns rank i's row band of the summed product: shape (M/n, N) —
    the tiled reduce-scatter ownership the sharded optimizer consumes.
    Elementwise-equal to the unfused scatter of the full product."""
    n = lax.psum(1, axis)
    (m, k), (_, cols) = a.shape, b.shape
    if m % n:
        raise HorovodTpuError(
            f"fused_matmul_reduce_scatter needs the output rows ({m}) "
            f"divisible by the axis size ({n})")
    col_bytes = max(1, m * a.dtype.itemsize)
    chunks = plan_chunks(cols, col_bytes, chunk_bytes=chunk_bytes,
                         align=1)
    outs = []
    for off, w in chunks:
        partial = _chunk_matmul(a, b[:, off:off + w])
        shard = lax.psum_scatter(partial, axis, scatter_dimension=0,
                                 tiled=True)
        outs.append(shard)
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if average:
        out = out / n
    return out


# ---------------------------------------------------------------------------
# (b) ZeRO-1 param-allgather fused with the first consuming matmul
# ---------------------------------------------------------------------------

def fused_allgather_matmul(x: jax.Array, w_shard: jax.Array, axis: str,
                           chunk_bytes: Optional[int] = None,
                           wire: Optional[str] = None) -> jax.Array:
    """``x @ all_gather(w_shard)ᵀ`` with the gather still in flight:
    the local (S, K) weight shard gathers in row chunks — reverse-
    availability order, i.e. the order the forward consumes them — and
    each gathered (n, w, K) band multiplies immediately, so the first
    matmul starts after ONE chunk's gather instead of the whole
    param buffer's.  `wire` rides the chunked quantized allgather
    (block-aligned, so bitwise-equal to the unfused wire gather).

    Returns (B, n*S): columns r*S..(r+1)*S hold x @ rank r's rows —
    exactly ``x @ lax.all_gather(w_shard, axis, tiled=True).T``."""
    codec = get_codec(wire)
    n = lax.psum(1, axis)
    s, k = w_shard.shape
    row_bytes = max(1, k * w_shard.dtype.itemsize)
    per_rank: List[List[jax.Array]] = [[] for _ in range(n)]
    for off, w in plan_chunks(s, row_bytes, chunk_bytes=chunk_bytes,
                              align=1):
        seg = w_shard[off:off + w]
        if codec.cooperative:
            from .quantized import quantized_allgather_shard
            flat = quantized_allgather_shard(
                seg.reshape(-1), axis, wire=codec.name)
            g = flat.reshape(n, w, k)
        else:
            g = lax.all_gather(seg, axis, tiled=False)
        for r in range(n):
            per_rank[r].append(_chunk_matmul(x, g[r].T))
    bands = [jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
             for cols in per_rank]
    return jnp.concatenate(bands, axis=1) if len(bands) > 1 else bands[0]


__all__ = [
    "fused_allgather_matmul",
    "fused_enabled",
    "fused_matmul_reduce_scatter",
    "fused_pallas_enabled",
    "pallas_matmul",
    "pipelined_allgather_shard",
    "pipelined_allreduce_shard",
    "pipelined_grouped_allreduce",
    "pipelined_psum_scatter",
    "plan_chunks",
]
