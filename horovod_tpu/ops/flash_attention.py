"""Pallas TPU flash attention (forward + backward).

The reference has NO attention kernels at all — it scales batch, never
sequence (SURVEY.md §5 "Long-context: absent").  Long context is
first-class in this framework (`parallel/sequence.py` ring/Ulysses);
this module supplies the missing on-chip piece: an O(T)-memory
blockwise attention kernel so the per-shard local attention never
materializes the [T, T] score matrix in HBM.

Algorithm: standard flash attention — online softmax over K/V blocks
with f32 running (m, l, acc) carried in VMEM scratch across the
sequential innermost grid dimension (the canonical TPU reduction
pattern, same as ops/pallas_kernels.py).  Backward recomputes P
blockwise from the saved per-row logsumexp L = m + log(l) and
accumulates dQ (grid over K blocks) and dK/dV (grid over Q blocks) in
separate kernels, as in the flash-attention-2 formulation.

Causal masking skips whole blocks strictly above the diagonal (they
contribute nothing), so causal costs ~half the FLOPs of full.  A
sliding `window` additionally skips blocks fully below the band
(O(T * window) compute); GQA/MQA (fewer K/V heads than Q heads) is
supported through the kv block index map — shared heads are read, not
materialized.  Neither exists anywhere in the reference (it has no
attention at all); they are part of this framework's long-context
edge next to ring/Ulysses sequence parallelism.

Layout: [B, T, H, D] API (matching parallel/sequence.py), kernels run
on [B*H, T, D] with block_q x block_k tiles (HOROVOD_FLASH_BLOCK_Q/K,
default 128 each — the r04 on-chip sweep's pick) and D untiled (D is
64-256 for every config here; padded to 128 lanes minimum by XLA).

MXU precision: the score / output / gradient matmuls run in the INPUT
dtype with f32 accumulation (`preferred_element_type`) — bf16 inputs
hit the MXU at the bf16 rate instead of paying the 4x f32 penalty —
while the online-softmax state (m, l, acc) and the p/ds intermediates
stay f32, the standard flash-attention-2 precision contract.

On the CPU backend the kernels run interpreted, which keeps the
numerics CI-covered without a chip (tests/test_flash_attention.py
checks fwd+grads against the dense oracle in parallel/sequence.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import util
from .pallas_kernels import _interpret

_NEG = -1e30
_BLOCK = 128  # default q/k block rows (= lane width)


def _fit_block(req: int, t: int) -> int:
    """Largest 128-multiple divisor of t not exceeding req (t % 128 == 0
    is validated upstream).  A requested tile that does not divide this
    T must not make a previously-working shape fail — a T=384 call with
    HOROVOD_FLASH_BLOCK_Q=256 runs at 128, it does not raise."""
    if req >= t:
        return t
    for m in range(min(req, t) // _BLOCK, 0, -1):
        if t % (m * _BLOCK) == 0:
            return m * _BLOCK
    # req < 128: _BLOCK always divides T (callers validate T % 128 == 0)
    # — never return a non-dividing tile, that would leave grid rows
    # unwritten.
    return _BLOCK


def _block_sizes(t: int, blocks=None):
    """(block_q, block_k): the caller's `blocks`, or from
    HOROVOD_FLASH_BLOCK_Q/K (default 128); clamped to the largest
    dividing tile for this T (see _fit_block)."""
    if blocks is not None:
        bq, bk = blocks
    else:
        bq = util.env_int("FLASH_BLOCK_Q", _BLOCK)
        bk = util.env_int("FLASH_BLOCK_K", _BLOCK)
    if bq <= 0 or bk <= 0:
        raise ValueError(
            f"HOROVOD_FLASH_BLOCK_Q/K must be positive, got ({bq}, {bk})")
    return _fit_block(bq, t), _fit_block(bk, t)


def _tc_params():
    """Mosaic grid semantics: batch*head and the outer seq dimension are
    parallel; the innermost dimension is the sequential online-softmax /
    accumulation walk ("arbitrary")."""
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def flash_routed(seq_len: int) -> bool:
    """Should attention at `seq_len` run the flash kernel?

    Forced by HOROVOD_FLASH_ATTENTION=1/0 when set.  AUTO when unset:
    on TPU, lengths >= HOROVOD_FLASH_ATTENTION_MIN_T (default 16384)
    route to flash — the r04 on-chip sweep (docs/PERF_NOTES.md) measured
    the XLA dense path OOM-ing at T=16384 (the f32 [T,T] score temp
    alone wants 34 GB at 32k) while flash runs 16k at 420 ms and 32k at
    1275 ms fwd+bwd; below the threshold XLA's fused dense attention
    ties or wins wall-clock (1.12x flash at 2k B4, 0.89-0.95x at
    4k-8k), so it stays the default there."""
    forced = util.getenv("FLASH_ATTENTION")
    if forced is not None and forced.strip() != "":
        # Empty string = unset (a CI default like FOO= must not force
        # dense and reintroduce the long-T OOM auto-routing prevents).
        return util.env_bool("FLASH_ATTENTION", False)
    if not util.is_tpu_backend():
        return False
    return seq_len >= util.env_int("FLASH_ATTENTION_MIN_T", 16384)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _apply_mask(s, qi, ki, bq, bk, causal, window, qs=None, ks=None):
    """Mask scores above the diagonal (causal), outside a sliding
    `window` band, and — with segment ids (packed sequences) — across
    segment boundaries.  Only blocks straddling a boundary actually mix
    masked/unmasked entries; causal/window blocks fully outside are
    skipped by the callers' pl.when gates (segment boundaries are
    data-dependent, so no static skip)."""
    if not causal and window is None and qs is None:
        return s
    keep = None
    if causal or window is not None:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            keep = q_pos >= k_pos
        if window is not None:
            wkeep = (q_pos - k_pos) < window
            keep = wkeep if keep is None else jnp.logical_and(keep, wkeep)
    if qs is not None:
        skeep = qs[:, None] == ks[None, :]
        keep = skeep if keep is None else jnp.logical_and(keep, skeep)
    return jnp.where(keep, s, _NEG)


def _block_gate(qi, ki, bq, bk, causal, window):
    """Whether block (qi, ki) can contain any unmasked entry: its k
    range [ki*bk, (ki+1)*bk) must intersect the allowed band
    [q - window + 1, q] for some q in [qi*bq, (qi+1)*bq)."""
    run = (ki == ki)  # all-true of the right traced type
    if causal:
        run = ki * bk < (qi + 1) * bq
    if window is not None:
        run = jnp.logical_and(
            run, (ki + 1) * bk - 1 >= qi * bq - (window - 1))
    return run


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, window,
                num_kb, bq, bk, has_seg):
    if has_seg:
        qs_ref, ks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        qs_ref = ks_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Blocks fully outside the causal / sliding-window band are skipped.
    run = _block_gate(qi, ki, bq, bk, causal, window)

    @pl.when(run)
    def _block():
        v = v_ref[0]                              # (bk, d) input dtype
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk) f32
        s = _apply_mask(s, qi, ki, bq, bk, causal, window,
                        None if qs_ref is None else qs_ref[0, :, 0],
                        None if ks_ref is None else ks_ref[0, :, 0])
        m_prev = m_scr[...]                       # (bq, 128) lanes equal
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)         # (bq, 128)
        p = jnp.exp(s - m_new[:, :1])              # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)             # (bq, 128)
        l_scr[...] = l_prev * corr + jnp.sum(
            p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = l_scr[...][:, :1]
        # Fully-masked rows (possible only with causal=False and all
        # -inf inputs) guard: l is > 0 in every supported path.
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, :, 0] = (m_scr[...] + jnp.log(l_scr[...]))[:, 0]


def _fwd(q3, k3, v3, seg, scale, causal, window, group, hq, blocks=None):
    """q3: (B*Hq, T, D), k3/v3: (B*Hkv, T, D) with T % block == 0 and
    group = Hq // Hkv; seg None or (B, T) int32 (hq = Hq, for the
    batch index map).  GQA never materializes repeated K/V: the index
    map points q-head b at kv-head b // group.  Returns (o, lse)."""
    bh, t, d = q3.shape
    bq, bk = _block_sizes(t, blocks)
    nq = t // bq
    nk = t // bk
    has_seg = seg is not None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, num_kb=nk, bq=bq, bk=bk,
                               has_seg=has_seg)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, bk, d),
                     lambda b, qi, ki: (b // group, ki, 0)),
        pl.BlockSpec((1, bk, d),
                     lambda b, qi, ki: (b // group, ki, 0)),
    ]
    operands = [q3, k3, v3]
    if has_seg:
        # Trailing singleton (like the lse output): TPU block tiling
        # wants the last dim 128-divisible or equal to the array dim,
        # which bq/bk below 128 would violate in the last position.
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b // hq, qi, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, qi, ki: (b // hq, ki, 0)),
        ]
        operands += [seg, seg]
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            # trailing singleton: TPU block tiling wants the last dim of
            # a block to be 128-divisible or equal to the array dim.
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_tc_params(),
        interpret=_interpret(),
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, window, num_kb, bq, bk,
                   has_seg):
    if has_seg:
        qs_ref, ks_ref, dq_ref, acc_scr = rest
    else:
        dq_ref, acc_scr = rest
        qs_ref = ks_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = _block_gate(qi, ki, bq, bk, causal, window)

    @pl.when(run)
    def _block():
        k = k_ref[0]
        lse = lse_ref[0, :, 0]                    # (bq,)
        delta = delta_ref[0, :, 0]                # (bq,)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _apply_mask(s, qi, ki, bq, bk, causal, window,
                        None if qs_ref is None else qs_ref[0, :, 0],
                        None if ks_ref is None else ks_ref[0, :, 0])
        p = jnp.exp(s - lse[:, None])             # (bq, bk) f32
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (bq, bk)
        ds = p * (dp - delta[:, None]) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, window, num_qb, bq, bk,
                    has_seg):
    if has_seg:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        qs_ref = ks_ref = None
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _block_gate(qi, ki, bq, bk, causal, window)

    @pl.when(run)
    def _block():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        s = _apply_mask(s, qi, ki, bq, bk, causal, window,
                        None if qs_ref is None else qs_ref[0, :, 0],
                        None if ks_ref is None else ks_ref[0, :, 0])
        p = jnp.exp(s - lse[:, None])                     # f32
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(res, g):
    (q3, k3, v3, seg, o3, lse, scale, causal, window, group,
     hq, blocks) = res
    has_seg = seg is not None
    do3 = g[0]                                   # input dtype (MXU rate)
    dlse = g[1]                                              # (bh, t, 1)
    bh, t, d = q3.shape
    bq, bk = _block_sizes(t, blocks)
    nq = t // bq
    nk = t // bk
    # delta_i = sum_d dO_i * O_i (rowwise, the flash-2 correction term),
    # minus the lse cotangent: dL/ds_ij = p_ij*(dp_ij - delta_i + dlse_i),
    # so dlse folds into delta with a sign flip.
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (bh, t, 1)
    # custom_vjp materializes an unused-lse cotangent as zeros, so this
    # is a no-op (zeros subtraction) on the plain flash_attention path.
    delta = delta - dlse.astype(jnp.float32)

    qspec = pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0))
    kspec = pl.BlockSpec((1, bk, d),
                         lambda b, qi, ki: (b // group, ki, 0))
    rowq = pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0))
    dq_specs = [qspec, kspec, kspec, qspec, rowq, rowq]
    dq_operands = [q3, k3, v3, do3, lse, delta]
    if has_seg:
        dq_specs += [
            pl.BlockSpec((1, bq, 1),
                         lambda b, qi, ki: (b // hq, qi, 0)),
            pl.BlockSpec((1, bk, 1),
                         lambda b, qi, ki: (b // hq, ki, 0)),
        ]
        dq_operands += [seg, seg]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, num_kb=nk, bq=bq, bk=bk,
                          has_seg=has_seg),
        grid=(bh, nq, nk),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_tc_params(),
        interpret=_interpret(),
    )(*dq_operands)

    # dk/dv: grid walks (kb outer, qb inner sequential).  Under GQA the
    # kernel produces PER-Q-HEAD partials (f32) and the group-sum
    # happens outside — revisiting one kv output block from g different
    # grid slots would be an accumulation race the Pallas output model
    # does not allow.
    qspec2 = pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qi, 0))
    kspec2 = pl.BlockSpec((1, bk, d),
                          lambda b, ki, qi: (b // group, ki, 0))
    ospec2 = pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0))
    rowq2 = pl.BlockSpec((1, bq, 1), lambda b, ki, qi: (b, qi, 0))
    dkv_specs = [qspec2, kspec2, kspec2, qspec2, rowq2, rowq2]
    dkv_operands = [q3, k3, v3, do3, lse, delta]
    if has_seg:
        dkv_specs += [
            pl.BlockSpec((1, bq, 1),
                         lambda b, ki, qi: (b // hq, qi, 0)),
            pl.BlockSpec((1, bk, 1),
                         lambda b, ki, qi: (b // hq, ki, 0)),
        ]
        dkv_operands += [seg, seg]
    out_dt = (k3.dtype, v3.dtype) if group == 1 else (jnp.float32,) * 2
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, num_qb=nq, bq=bq, bk=bk,
                          has_seg=has_seg),
        grid=(bh, nk, nq),
        in_specs=dkv_specs,
        out_specs=[ospec2, ospec2],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), out_dt[0]),
                   jax.ShapeDtypeStruct((bh, t, d), out_dt[1])],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_tc_params(),
        interpret=_interpret(),
    )(*dkv_operands)
    if group > 1:
        dk = dk.reshape(-1, group, t, d).sum(axis=1).astype(k3.dtype)
        dv = dv.reshape(-1, group, t, d).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash3(q3, k3, v3, seg, causal, window, group, hq, blocks):
    return _fwd(q3, k3, v3, seg, 1.0 / math.sqrt(q3.shape[-1]), causal,
                window, group, hq, blocks)


def _flash3_fwd(q3, k3, v3, seg, causal, window, group, hq, blocks):
    scale = 1.0 / math.sqrt(q3.shape[-1])
    o, lse = _fwd(q3, k3, v3, seg, scale, causal, window, group, hq,
                  blocks)
    return (o, lse), (q3, k3, v3, seg, o, lse, scale, causal, window,
                      group, hq, blocks)


def _flash3_bwd(causal, window, group, hq, blocks, res, g):
    return _bwd(res, g)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def validate_window(window, causal):
    """Shared window/causal contract for EVERY attention entry point
    (flash kernel, dense oracle, ring) — one definition so the three
    paths cannot drift (r4 review)."""
    if window is None:
        return
    if not causal:
        raise ValueError(
            "window requires causal=True (a non-causal symmetric band "
            "is not implemented)")
    if int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _check_and_to3(q, k, v, window=None, causal=True,
                   segment_ids=None):
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if k.shape != v.shape or k.shape[0] != B or k.shape[1] != T \
            or k.shape[3] != D or H % max(Hkv, 1):
        raise ValueError(
            f"flash_attention: incompatible shapes q={tuple(q.shape)} "
            f"k={tuple(k.shape)} v={tuple(v.shape)} (GQA needs "
            f"n_heads % n_kv_heads == 0)")
    if not (q.dtype == k.dtype == v.dtype):
        # The kernels run the MXU matmuls in the input dtype, so all
        # three operands must agree (upcast q/k/v consistently upstream).
        raise ValueError(
            f"flash_attention needs matching q/k/v dtypes, got "
            f"({q.dtype}, {k.dtype}, {v.dtype})")
    if T % _BLOCK:
        raise ValueError(
            f"flash_attention needs seq len % {_BLOCK} == 0, got {T}")
    validate_window(window, causal)
    seg3 = None
    if segment_ids is not None:
        if tuple(segment_ids.shape) != (B, T):
            raise ValueError(
                f"flash_attention: segment_ids must be (batch, seq) = "
                f"({B}, {T}), got {tuple(segment_ids.shape)}")
        # Trailing singleton for TPU-legal block tiling (see _fwd).
        seg3 = jnp.asarray(segment_ids, jnp.int32)[:, :, None]

    def to3(x, h):
        return x.transpose(0, 2, 1, 3).reshape(B * h, T, D)

    return (B, T, H, Hkv, D), to3(q, H), to3(k, Hkv), to3(v, Hkv), seg3


def flash_attention(q, k, v, causal: bool = True, window=None,
                    segment_ids=None, blocks=None):
    """Flash attention on [B, T, H, D] (same convention as
    parallel/sequence.py), differentiable, O(T) memory.

    T must be a multiple of 128 (pad upstream; the transformer configs
    here use power-of-two T).  Numerics: f32 accumulation; output in
    q.dtype; matches `parallel.sequence.dense_attention_oracle` to f32
    noise.

    GQA/MQA: k/v may carry fewer heads than q (H % Hkv == 0); q head h
    attends kv head h // (H // Hkv).  The kernel reads the shared K/V
    blocks through its index map — the repeated heads are never
    materialized in HBM.

    `window` (requires causal): sliding-window attention — each query
    sees at most the last `window` keys; blocks fully outside the band
    are skipped on both sides, so compute scales O(T * window).

    `segment_ids` ([B, T] int): packed-sequence block-diagonal masking —
    tokens attend only within their own segment, so multiple documents
    packed into one row never cross-attend.

    `blocks` (block_q, block_k): the kernels' tiles for this call, each
    clamped to a divisor of T; None: HOROVOD_FLASH_BLOCK_Q/K (128).  A
    grid step costs about a third of a microsecond whatever it holds,
    so long sequences want larger tiles than 128 (PERF.md, PR 37)."""
    window = None if window is None else int(window)
    (B, T, H, Hkv, D), q3, k3, v3, seg = _check_and_to3(
        q, k, v, window, causal, segment_ids)
    o3, _ = _flash3(q3, k3, v3, seg, causal, window, H // Hkv, H,
                    None if blocks is None else tuple(blocks))
    return o3.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def flash_attention_lse(q, k, v, causal: bool = True, window=None,
                        segment_ids=None):
    """Like `flash_attention` but also returns the per-row logsumexp
    (f32, [B, T, H]) — the merge weight ring attention needs to combine
    per-pair partial results (both outputs are differentiable)."""
    window = None if window is None else int(window)
    (B, T, H, Hkv, D), q3, k3, v3, seg = _check_and_to3(
        q, k, v, window, causal, segment_ids)
    o3, lse3 = _flash3(q3, k3, v3, seg, causal, window, H // Hkv, H,
                       None)
    o = o3.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    lse = lse3.reshape(B, H, T).transpose(0, 2, 1)
    return o, lse


__all__ = ["flash_attention", "flash_attention_lse", "flash_routed"]
