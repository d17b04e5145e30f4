"""Pallas TPU retention decode step: a layer's whole pass over the
state, one read and one write, in the carried array itself, over the
rows that are live.

A retention layer's decode step (models/decode.py
`_retention_decode_layer`) reads `phi(q)^T S` and `phi(q)^T z` out of
each row's state as it was and leaves `decay S + phi(k) v^T`, `decay z +
phi(k)` behind.  As einsums (`_state_pass`) that is three passes: XLA
reads the state for the read-out, and reads and writes it again for the
update: at 16 rows x 8 kv heads x 8320 x 128 float32 a layer, 20.4 of a
served step's 30.9 ms where one read and one write need 10.7 (PERF.md,
PR 39).  An idle row of a served batch (`pos` 0) is read and written
like any other, though its state is nobody's: it is installed whole when
a request boards.

Form, as ops/decode_attention.py: NO grid, since a row passed over has
to cost nothing; one invocation walks the rows in a loop and skips those
whose `live` entry is 0.  The kernel is handed the WHOLE stacked leaves
`cs` [L, B, Hkv, Df, Dh] and `cz` [L, B, Hkv, Df] in HBM
(`memory_space=ANY`; a slice handed to a kernel is a copy) with the
layer's index as a prefetched scalar, and both are aliased onto its
outputs, so a step's donated cache comes back in the same buffers and
nothing of another layer or of an idle row is touched.  Of a live row it
walks the kv heads and, a head, tiles of `T` rows of `Df` (`tile_rows`:
1664 of 8320): a tile [T, Dh] is copied into one of two VMEM buffers
while the one before is worked on (the head's next tile, the next head's
first, or the next live row's first, so that no boundary stalls), read
out, and written back from one of two out-buffers, its copy out in
flight under the next tile's work.  A row's normaliser [Hkv, Df] comes
and goes once a row, phi(q) and phi(k) once a head, both a step ahead.

A tile's work: `num += fq_t . S_t`, a float32 matrix product ([R, T] x
[T, Dh] at `Precision.HIGHEST`: the state is read as float32, as the
einsum reads it), and `S_t <- decay S_t + fk_t v^T` on the vector unit,
phi(k)'s tile turned into a column by a transpose.  `fq` and `fk` ride
together as the rows of one [R, T] block a tile (`g` rows of phi(q), one
of phi(k), zeros up to a multiple of 8), so one copy and one transpose
serve both.  The decay of `num` and `den`, the token's own term and the
division stay outside, in XLA, on [B, Hkv, g, Dh] numbers.

Compiled for a described v5e the tile's loop is 1431 bundles, the
matrix unit's weight pushes (six a vreg of state: the float32 product's
passes) the most of them; with the read-out on the vector unit instead
(phi(q)'s columns broadcast along lanes, six permutes a vreg) it is
3415.  On the chip the first is bound by its copies and the read-out
costs nothing beside them (8 layers of 16 x 8 x 8320 x 128: 14.8 ms for
the einsums' 21.3, 15.3 with no read-out at all), the second by its
work (17.4); tiles of 640 rows are 6% slower (PERF.md, PR 39).

On the CPU backend the kernel runs interpreted (`_interpret()`):
tests/test_retention_step.py holds it against the einsums on the same
inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _LANES, _interpret

#: rows of the state a tile may hold at most: four buffers of
#: [2048, 128] float32 are 4 MB of VMEM
_MAX_TILE = 2048


def tile_rows(features: int) -> int:
    """Rows of a state of `features` rows that a tile holds: the largest
    multiple of 128 that divides them, up to `_MAX_TILE` (1664 of 8320 =
    65 x 128); 0 where none does."""
    if features % _LANES:
        return 0
    n = features // _LANES
    return _LANES * max(d for d in range(1, n + 1)
                        if n % d == 0 and d * _LANES <= _MAX_TILE)


def takes(cs) -> bool:
    """Does the kernel make the pass over a state stacked as `cs`
    [L, B, Hkv, Df, Dh]?  A float32 state whose rows of `Dh` fill whole
    lanes and whose `Df` tiles; everything else is the einsums'
    (models/decode.py `_state_pass`)."""
    return (cs.dtype == jnp.float32 and cs.shape[-1] % _LANES == 0
            and tile_rows(cs.shape[-2]) > 0)


def read_pct(positions) -> float:
    """Rows whose state the kernel reads in a step over rows at
    `positions` (a host array; 0 is an idle row), over the rows, %:
    `_kernel`'s walk, on the host."""
    pos = np.asarray(positions)
    return 100.0 * int((pos > 0).sum()) / len(pos)


def _kernel(layer_ref, live_ref, decay_ref, fqk_hbm, v_ref, cs_hbm, cz_hbm,
            cs_out, cz_out, num_ref, den_ref,
            sbuf, obuf, fbuf, zbuf, sem, *, g):
    B, Hkv, nT, R, T = fqk_hbm.shape
    Dh = v_ref.shape[-1]
    layer = layer_ref[0]
    per_row = Hkv * nT

    def next_row(b):
        """First live row past `b`; B when none."""
        return lax.while_loop(
            lambda r: jnp.logical_and(
                r < B, live_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1, b + 1)

    def rows_of(k):
        return pl.ds(pl.multiple_of(k * T, T), T)

    def tile_in(b, h, k, slot):
        return pltpu.make_async_copy(
            cs_hbm.at[layer, b, h, rows_of(k), :], sbuf.at[slot],
            sem.at[0, slot])

    def tile_out(b, h, k, slot):
        return pltpu.make_async_copy(
            obuf.at[slot], cs_out.at[layer, b, h, rows_of(k), :],
            sem.at[1, slot])

    def head_in(b, h, slot):
        return pltpu.make_async_copy(fqk_hbm.at[b, h], fbuf.at[slot],
                                     sem.at[2, slot])

    def z_in(b, slot):
        return pltpu.make_async_copy(cz_hbm.at[layer, b], zbuf.at[slot],
                                     sem.at[3, slot])

    def z_out(b, slot):
        return pltpu.make_async_copy(zbuf.at[slot], cz_out.at[layer, b],
                                     sem.at[4, slot])

    first = next_row(-1)

    @pl.when(first < B)
    def _prime():
        z_in(first, 0).start()
        head_in(first, 0, 0).start()
        tile_in(first, 0, 0, 0).start()

    def row(b, done):               # `done`: live rows walked before this
        rs = done % 2
        nxt = next_row(b)

        # the other normaliser buffer is free once the row before has
        # sent its own back
        @pl.when(done > 0)
        def _():
            z_out(b, 1 - rs).wait()

        @pl.when(nxt < B)
        def _():
            z_in(nxt, 1 - rs).start()

        z_in(b, rs).wait()

        for h in range(Hkv):
            fs = (done * Hkv + h) % 2
            if h + 1 < Hkv:
                head_in(b, h + 1, 1 - fs).start()
            else:
                @pl.when(nxt < B)
                def _():
                    head_in(nxt, 0, 1 - fs).start()
            head_in(b, h, fs).wait()
            decay = decay_ref[b, h]
            v_row = v_ref[b, h:h + 1, :]                     # [1, Dh]

            def tile(k, carry):
                acc, den = carry
                u = done * per_row + h * nT + k     # tiles walked so far
                slot = u % 2

                # the next tile's copy is in flight while this one is
                # worked on: the head's next, the row's next head's
                # first, or the next live row's first
                @pl.when(k + 1 < nT)
                def _():
                    tile_in(b, h, k + 1, 1 - slot).start()

                if h + 1 < Hkv:
                    @pl.when(k + 1 == nT)
                    def _():
                        tile_in(b, h + 1, 0, 1 - slot).start()
                else:
                    @pl.when(jnp.logical_and(k + 1 == nT, nxt < B))
                    def _():
                        tile_in(nxt, 0, 0, 1 - slot).start()

                tile_in(b, h, k, slot).wait()

                # this out-buffer's write of two tiles ago
                @pl.when(u >= 2)
                def _():
                    tile_out(b, h, k, slot).wait()

                s = sbuf[slot]                               # [T, Dh]
                fq_t = fbuf[fs, k]                           # [R, T]
                z_t = zbuf[rs, h:h + 1, rows_of(k)]          # [1, T]
                den = den + jnp.sum(fq_t * z_t, axis=1, keepdims=True)
                zbuf[rs, h:h + 1, rows_of(k)] = (
                    decay * z_t + fq_t[g:g + 1, :])
                acc = acc + jnp.dot(
                    fq_t, s, precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                fk_col = fq_t.T[:, g:g + 1]                  # [T, 1]
                obuf[slot] = decay * s + fk_col * v_row
                tile_out(b, h, k, slot).start()
                return acc, den

            acc, den = lax.fori_loop(
                0, nT, tile, (jnp.zeros((R, Dh), jnp.float32),
                              jnp.zeros((R, 1), jnp.float32)))
            num_ref[b, h] = acc
            den_ref[b, h] = jnp.broadcast_to(den, (R, _LANES))

        z_out(b, rs).start()
        return done + 1

    done = lax.fori_loop(
        0, B, lambda b, done: lax.cond(live_ref[b] != 0,
                                       lambda: row(b, done), lambda: done),
        0)

    @pl.when(done > 0)
    def _drain():
        z_out(0, (done - 1) % 2).wait()
        tile_out(0, 0, 0, 0).wait()
        if per_row > 1:
            tile_out(0, 0, 0, 1).wait()


def retention_step(fq, fk, v0, decay, cs, cz, layer, live=None):
    """One layer's pass over the state of the rows that are live.

    fq [B, Hkv, g, Df], fk [B, Hkv, Df]: phi of the token's queries and
    key, float32; v0 [B, Hkv, Dh] its value, float32; decay [B, Hkv];
    cs [L, B, Hkv, Df, Dh], cz [L, B, Hkv, Df] the WHOLE stacked states
    and normalisers, float32 (`takes(cs)`); `layer` the layer's index
    into them (traced or not); `live` [B] bool, or None for every row.
    Returns (num [B, Hkv, g, Dh], den [B, Hkv, g], cs, cz): `fq . S`
    and `fq . z` out of layer `layer` AS IT WAS, before the decay, 0 for
    a row that is not live, and the stacked leaves, in the arguments'
    own buffers where they were donated, with `decay S + fk v0^T` and
    `decay z + fk` in the live rows of that layer and nothing else
    touched: what `_state_pass` (models/decode.py) gives for every row.
    """
    B, Hkv, g, Df = fq.shape
    Dh = v0.shape[-1]
    T = tile_rows(Df)
    nT = Df // T
    R = -(-(g + 1) // 8) * 8
    # phi(q)'s g rows and phi(k)'s one, zeros up to R: a block a tile
    fqk = jnp.pad(jnp.concatenate([fq, fk[:, :, None, :]], axis=2),
                  ((0, 0), (0, 0), (0, R - g - 1), (0, 0)))
    fqk = fqk.reshape(B, Hkv, R, nT, T).transpose(0, 1, 3, 2, 4)
    mask = (jnp.ones((B,), jnp.int32) if live is None
            else live.astype(jnp.int32))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    whole = lambda *shape: pl.BlockSpec(
        shape, lambda i, *_: (0,) * len(shape))
    cs, cz, num, den = pl.pallas_call(
        functools.partial(_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),      # decay
                any_,                                       # fqk
                whole(B, Hkv, Dh),                          # v0
                any_, any_,                                 # cs, cz
            ],
            out_specs=[any_, any_, whole(B, Hkv, R, Dh),
                       whole(B, Hkv, R, _LANES)],
            scratch_shapes=[
                pltpu.VMEM((2, T, Dh), jnp.float32),
                pltpu.VMEM((2, T, Dh), jnp.float32),
                pltpu.VMEM((2, nT, R, T), jnp.float32),
                pltpu.VMEM((2, Hkv, Df), jnp.float32),
                pltpu.SemaphoreType.DMA((5, 2)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(cs.shape, cs.dtype),
                   jax.ShapeDtypeStruct(cz.shape, cz.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, R, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, R, _LANES), jnp.float32)],
        # operands count the prefetched scalars: cs is the sixth
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=_interpret(),
        name="retention_step",
    )(layer, mask, decay, fqk, v0, cs, cz)
    num, den = num[:, :, :g], den[:, :, :g, 0]
    if live is not None:
        # a row that was passed over wrote nothing
        num = jnp.where(live[:, None, None, None], num, 0.0)
        den = jnp.where(live[:, None, None], den, 0.0)
    return num, den, cs, cz
