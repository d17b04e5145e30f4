"""Pallas TPU kernels for the Adasum hot path.

Reference parity: horovod/common/ops/adasum/adasum.h
`DispatchComputeDotAndNormSqrds` / `DispatchScaledAdd` — the reference's
hand-written (templated C++, vectorized fp16) inner loops that compute
a·b, ‖a‖², ‖b‖² and the scaled combination for every pairwise Adasum
level.  Those are exactly the memory-bound passes worth owning on TPU:
this module fuses the three reductions into ONE pass over HBM (a and b
are each read once, f32 accumulation in VMEM regardless of input dtype)
instead of relying on XLA to fuse three separate reductions.

Layout: inputs are flattened and padded to (rows, 128) lane tiles
(zeros are exact no-ops for dot/norm sums); the grid walks row blocks
sequentially per batch element, accumulating into an SMEM (1, 4)
accumulator block (TPU grids execute sequentially per core, so
read-modify-write across grid steps is the canonical reduction
pattern).

On the CPU backend the same kernels run under the Pallas interpreter
(`_interpret()`), so the numerics are CI-covered without a chip.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import util
from ..common.exceptions import HorovodTpuError

_LANES = 128
# 1024 rows x 128 lanes = best of the measured block sizes (v5e, 64 MB
# bf16 pair combine: 256→4.89 ms, 512→4.68, 1024→4.62); multiple of the
# bf16 sublane tile (16), ~0.5 MiB/input block in VMEM.
_BLOCK_ROWS = 1024


def _interpret() -> bool:
    """Interpret mode exactly when the call will run on the CPU backend;
    on a TPU every kernel goes through Mosaic."""
    return jax.default_backend() == "cpu"


def pallas_enabled(n_elements: int) -> bool:
    """Opt-in via HOROVOD_ADASUM_PALLAS=1.

    Measured on v5e (64 MB bf16 pair combine, true-sync timing): XLA's
    own fusion of the three reductions + scaled add runs 3.76 ms vs
    4.62 ms for these kernels — the combine is bandwidth-bound and the
    compiler's pipelining wins, so the default stays XLA ("don't
    hand-schedule what the compiler already does").  The kernels remain
    the substrate for variants XLA cannot fuse (quantized/fp8 wire
    formats, fused ppermute+combine ladders).
    """
    if n_elements < _LANES:
        return False
    return util.env_bool("ADASUM_PALLAS", False)


def _tile(x: jax.Array) -> Tuple[jax.Array, int]:
    """(k, n) → (k, rows, 128) zero-padded to whole row blocks."""
    k, n = x.shape
    per_block = _BLOCK_ROWS * _LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    if padded != n:
        x = jnp.pad(x, ((0, 0), (0, padded - n)))
    return x.reshape(k, padded // _LANES, _LANES), padded // _LANES


def _dot_norms_kernel(a_ref, b_ref, out_ref):
    # out_ref is the WHOLE (k, 4) SMEM accumulator (TPU lowering requires
    # un-blocked SMEM outputs); this batch row's slot is program_id(0).
    bi = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[bi, 0] = 0.0
        out_ref[bi, 1] = 0.0
        out_ref[bi, 2] = 0.0
        out_ref[bi, 3] = 0.0

    af = a_ref[0].astype(jnp.float32)
    bf = b_ref[0].astype(jnp.float32)
    out_ref[bi, 0] += jnp.sum(af * bf)
    out_ref[bi, 1] += jnp.sum(af * af)
    out_ref[bi, 2] += jnp.sum(bf * bf)


def fused_dot_norms(a: jax.Array, b: jax.Array) -> jax.Array:
    """One-pass [a·b, ‖a‖², ‖b‖²] per batch row, f32 accumulation.

    a, b: (k, n) same shape/dtype.  Returns (k, 3) float32.
    Reference: adasum.h DispatchComputeDotAndNormSqrds (which the MPI
    path runs over vector halves at every VHDD level).
    """
    if a.shape != b.shape:
        raise HorovodTpuError(
            f"fused_dot_norms: shape mismatch {a.shape} vs {b.shape}")
    k, _ = a.shape
    at, rows = _tile(a)
    bt, _ = _tile(b)
    grid = (k, rows // _BLOCK_ROWS)
    out = pl.pallas_call(
        _dot_norms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _BLOCK_ROWS, _LANES), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, _BLOCK_ROWS, _LANES), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((k, 4), jnp.float32),
        interpret=_interpret(),
    )(at, bt)
    return out[:, :3]


def _scaled_add_kernel(ca_ref, cb_ref, a_ref, b_ref, out_ref):
    bi = pl.program_id(0)
    af = a_ref[0].astype(jnp.float32)
    bf = b_ref[0].astype(jnp.float32)
    out_ref[0] = (ca_ref[bi] * af + cb_ref[bi] * bf).astype(out_ref.dtype)


def fused_scaled_add(ca: jax.Array, cb: jax.Array,
                     a: jax.Array, b: jax.Array) -> jax.Array:
    """out = ca*a + cb*b per batch row, computed at f32, cast back to the
    input dtype (reference: adasum.h DispatchScaledAdd).  ca/cb: (k,)
    f32 scalars prefetched to SMEM."""
    k, n = a.shape
    at, rows = _tile(a)
    bt, _ = _tile(b)
    grid = (k, rows // _BLOCK_ROWS)
    out = pl.pallas_call(
        _scaled_add_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, _BLOCK_ROWS, _LANES), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, _BLOCK_ROWS, _LANES), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, _BLOCK_ROWS, _LANES),
                               lambda bi, ci: (bi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct(at.shape, a.dtype),
        interpret=_interpret(),
    )(ca, cb, at, bt)
    return out.reshape(k, rows * _LANES)[:, :n]


_EPS = 1e-30


def pallas_pair_combine_batched(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched Adasum pair combination through the fused kernels.

    a, b: (k, *shape).  adasum(a,b) = (1 - a·b/2‖a‖²)a + (1 - a·b/2‖b‖²)b
    with zero-norm guards matching ops/adasum.py's jnp path bit-for-bit
    at f32.
    """
    k = a.shape[0]
    shape = a.shape[1:]
    a2 = a.reshape(k, -1)
    b2 = b.reshape(k, -1)
    d = fused_dot_norms(a2, b2)
    dot, na, nb = d[:, 0], d[:, 1], d[:, 2]
    ca = jnp.where(na > _EPS, 1.0 - dot / (2.0 * jnp.maximum(na, _EPS)), 1.0)
    cb = jnp.where(nb > _EPS, 1.0 - dot / (2.0 * jnp.maximum(nb, _EPS)), 1.0)
    out = fused_scaled_add(ca.astype(jnp.float32), cb.astype(jnp.float32),
                           a2, b2)
    return out.reshape((k,) + shape)


__all__ = [
    "fused_dot_norms",
    "fused_scaled_add",
    "pallas_enabled",
    "pallas_pair_combine_batched",
]
