"""Pallas TPU decode attention: one query a row against the LIVE slots
of that row's ring, read where the stacked cache lies.

The decode step's attention (models/decode.py `_decode_layer`, c == 1) is
two contractions of a [g, Dh] query group against a row's keys and
values.  As einsums over the view they read every slot of every row,
whatever is live: at 32 rows x 3584 slots with 8 rows of a thousand
tokens, 93% of what they read is masked off afterwards (PERF.md, PR 35).
A row at `pos` holds `min(pos + 1, S)` live slots, the first ones while
its ring has not wrapped, and an idle row of a served batch sits at
`pos` 0.  This kernel reads blocks `0 .. ceil(min(pos + 1, S) / block)
- 1` of each row and nothing of a row at `pos` 0.

Form.  A skipped block has to cost nothing, and a grid step costs 0.35
us whether it works or not, so the kernel has NO grid over rows or
blocks: one invocation walks the rows in a loop and each row's live
blocks in a loop of dynamic length.  K and V stay in HBM
(`memory_space=ANY`): the kernel is handed the WHOLE stacked leaves
[L, B, Hkv, S, Dh] with the layer's index as a prefetched scalar (a
slice handed to a kernel is a copy), and copies one block of all kv
heads, [Hkv, block, Dh], at a time into one of two VMEM buffers; while
a block is computed on, the next one is in flight: the row's next
block, or behind a row's last block the first block of the next row
that has any, so that a row boundary stalls nothing.  The walk over
blocks is an online softmax (running max, sum and accumulator a kv
head, in float32), the products run in the cache's dtype with float32
accumulation, and the second one takes `p` rounded to that dtype, as
the flash prefill kernel does (ops/flash_attention.py).

The mask is `_decode_layer`'s, on the slot's reconstructed absolute
position (`>= 0`, `<= pos`, inside the window), so a wrapped ring, where
every block is live, gives what the einsum gives.  A last block that
would run past `S` (3840 slots against blocks of 512) starts at
`S - block` instead and masks the slots the block before it held.

A row at `pos` 0 sees one slot, the one just written, with weight
exactly 1: its output is that slot's V, which the wrapper selects
outside the kernel (`B x Hkv` vectors), so that idle rows start no copy.

A latent kind's leaves (`latent=True`; models/decode.py, "Latent
attention") take the same walk: one head of latents [block, 512] and one
of shared keys [block, 128] (64 numbers and 64 zeros: whole tiles) a
copy, every query head against them (g is the head count), the block of
latents used twice, as key and as value, and the scale an argument.
Such a read is 121 operations a byte where the grouped-query read is 4:
nearer the chip's ridge than its memory roof, and it still waits for the
copies.

On the CPU backend the kernel runs interpreted (`_interpret()`), which
keeps its numerics covered without a chip (tests/test_decode_attention
.py, against the einsum on the same inputs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

_NEG = -1e30
#: slots a block: what one copy brings in a kv head, and the grain at
#: which dead slots are skipped
BLOCK = 512


def reads_live(slots: int) -> bool:
    """Does a ring of `slots` span enough blocks for the kernel to skip
    any?  One block or less is the einsum's (models/decode.py), and so
    is a ring whose last block would start off the 8-slot tiles the
    leaves lie in (Mosaic refuses the copy's slice)."""
    return slots >= 2 * BLOCK and slots % 8 == 0


def read_pct(positions, slots: int, block: int = BLOCK) -> float:
    """Blocks the kernel reads of a view of `slots` slots a row whose
    rows stand at `positions` (a host array; 0 is an idle row), over the
    blocks the view holds, %: `_kernel`'s count of a row's live blocks,
    on the host."""
    pos = np.asarray(positions)
    live = np.minimum(pos + 1, slots)[pos > 0]
    held = len(pos) * -(-slots // block)
    return 100.0 * int((-(-live // block)).sum()) / held


def _kernel(layer_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, *, scale, window, block, latent):
    B, Hkv, g, _ = q_ref.shape
    Dv = o_ref.shape[-1]
    S = k_hbm.shape[3]
    layer = layer_ref[0]

    def next_row(b):
        """First row past `b` with a block to read; B when none."""
        return lax.while_loop(
            lambda r: jnp.logical_and(r < B, pos_ref[jnp.minimum(r, B - 1)]
                                      == 0),
            lambda r: r + 1, b + 1)

    def block_start(j):
        return jnp.minimum(j * block, S - block)

    def copies(b, j, buf):
        at = pl.ds(block_start(j), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, :, at, :],
                                      kbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, b, :, at, :],
                                      vbuf.at[buf], sem.at[1, buf]))

    def start(b, j, buf):
        for c in copies(b, j, buf):
            c.start()

    first = next_row(-1)

    @pl.when(first < B)
    def _prime():
        start(first, 0, 0)

    def row(b, done):               # `done`: blocks walked before this row
        pos = pos_ref[b]
        # live blocks; a row at depth 0 is the wrapper's (`read_pct`
        # counts the same on the host)
        n = jnp.where(pos > 0, pl.cdiv(jnp.minimum(pos + 1, S), block), 0)
        ring = pos % S

        def one_block(j, carry):
            buf = (done + j) % 2

            @pl.when(j + 1 < n)
            def _same_row():
                start(b, j + 1, 1 - buf)

            @pl.when(j + 1 == n)
            def _next_row():
                nxt = next_row(b)

                @pl.when(nxt < B)
                def _():
                    start(nxt, 0, 1 - buf)

            for c in copies(b, j, buf):
                c.wait()

            # slot -> the absolute position it holds after the write at
            # `pos`: pos - ((pos - slot) mod S), without the vector mod
            slot = block_start(j) + lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            held = pos - ring + slot - jnp.where(slot > ring, S, 0)
            valid = jnp.logical_and(held >= 0, slot >= j * block)
            if window:
                valid = jnp.logical_and(valid, pos - held < window)

            out = []
            for h in range(Hkv):
                m, l, acc = carry[h]
                k, v = kbuf[buf, h], vbuf[buf, h]           # [block, Dh]
                contract = (((1,), (1,)), ((), ()))
                if latent:
                    # the first leaf is the latent, scored against the
                    # query's first `Dv` numbers and summed as the value;
                    # the second the shared key, against the rest
                    s = lax.dot_general(
                        q_ref[b, h, :, :Dv], k, contract,
                        preferred_element_type=jnp.float32)
                    s = (s + lax.dot_general(
                        q_ref[b, h, :, Dv:], v, contract,
                        preferred_element_type=jnp.float32)) * scale
                    v = k
                else:
                    s = lax.dot_general(
                        q_ref[b, h], k, contract,
                        preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, _NEG)               # [g, block]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = alpha * acc + jnp.dot(
                    p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                out.append((m_new, l, acc))
            return tuple(out)

        init = tuple((jnp.full((g, 1), _NEG, jnp.float32),
                      jnp.zeros((g, 1), jnp.float32),
                      jnp.zeros((g, Dv), jnp.float32))
                     for _ in range(Hkv))
        state = lax.fori_loop(0, n, one_block, init)
        for h, (_, l, acc) in enumerate(state):
            # a row that read nothing leaves 0 / 1
            o_ref[b, h] = acc / jnp.where(l > 0, l, 1.0)
        return done + n

    lax.fori_loop(0, B, row, 0)


def decode_attention(q, ck, cv, layer, pos, *, window: int = 0,
                     block: int = BLOCK, scale=None, latent: bool = False):
    """Attention of one query a row over that row's live slots.

    q [B, Hkv, g, Dh], rotated; ck, cv [L, B, Hkv, S, Dh], the WHOLE
    stacked leaves, the row's new K and V already written at
    `pos % S`; `layer` the layer's index into them (traced or not);
    `pos` [B] int32, each row's depth; `window` the layer's attention
    window, 0 for none.  `S >= block`.  Returns o [B, Hkv, g, Dh] float32:
    softmax(q . K / sqrt(Dh)) . V over the slots whose absolute position
    is in `(pos - window, pos]`; `scale` replaces `1 / sqrt(Dh)`.

    `latent`: the leaves are a latent kind's (models/decode.py, "Latent
    attention"), ck [L, B, 1, S, R] the latents and cv [L, B, 1, S, Dr]
    the key all heads share (R and Dr whole tiles of 128 lanes); q
    [B, 1, H, R + Dr] is every head's absorbed query beside its rotated
    part, the score of a slot is `scale (q[:R] . c + q[R:] . r)` and the
    values ARE the latents: z [B, 1, H, R] float32.  A block of latents
    is copied once and used for both.
    """
    B, Hkv, g, Dh = q.shape
    Dv = ck.shape[-1] if latent else Dh
    S = ck.shape[3]
    if S < block:
        raise ValueError(f"a ring of {S} slots holds no block of {block}")
    pos = pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    o = pl.pallas_call(
        functools.partial(
            _kernel, scale=1.0 / (Dh ** 0.5) if scale is None else scale,
            window=window, block=block, latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((B, Hkv, g, Dh), lambda i, *_: (0, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((B, Hkv, g, Dv),
                                   lambda i, *_: (0, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, block, ck.shape[-1]), ck.dtype),
                pltpu.VMEM((2, Hkv, block, cv.shape[-1]), cv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dv), jnp.float32),
        interpret=_interpret(),
        name="decode_attention",
    )(layer.reshape(1), pos, q, ck, cv)
    # A row at depth 0 sees the slot it just wrote and nothing else.
    first = lax.dynamic_slice(ck if latent else cv, (layer, 0, 0, 0, 0),
                              (1, B, Hkv, 1, Dv))
    return jnp.where((pos == 0)[:, None, None, None],
                     first[0].astype(jnp.float32), o)
