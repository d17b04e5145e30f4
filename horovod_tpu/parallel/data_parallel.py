"""Data-parallel step compilation and the gradient-tape analog.

Reference parity (SURVEY.md §2.4, §3.3–3.4):
  - hvd.DistributedGradientTape (tensorflow/__init__.py `_allreduce_grads`)
      → `DistributedGradientTape` / `distributed_grad`
  - the torch hook-per-param overlap machinery (torch/optimizer.py)
      → subsumed by XLA's latency-hiding scheduler: gradient psums issued
        inside the compiled step overlap backward compute automatically,
        which is the compiler doing what Horovod's background thread +
        grad-ready hooks do by hand.

TPU-native redesign: the money path is ONE compiled SPMD program per step.
`data_parallel(step_fn)` wraps a per-rank step function with
`shard_map` over the global mesh — batch sharded over the `hvd` axis,
params/optimizer state replicated — and jits it with donation so weights
update in place in HBM.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..common import basics, util
from ..common.basics import GLOBAL_AXIS, ProcessSet
from ..metrics import catalog as _met
from ..ops import collectives as C
from ..ops import wire as _wire
from ..ops.compression import Compression, NoneCompressor
from ..utils import timeline as _tl


def shard_batch(batch: Any, mesh: Optional[Mesh] = None) -> Any:
    """Place a host batch pytree onto the mesh, sharded on dim 0 over the
    `hvd` axis (the input-pipeline half of data parallelism).

    Each process passes the rows of ITS OWN ranks — Horovod's contract:
    every rank feeds its own batch.  With one process that is the whole
    batch; with several, the global batch is their concatenation in rank
    order (a plain `device_put` onto a mesh that spans processes would
    instead demand the same full batch from every process)."""
    mesh = mesh or basics.global_mesh()
    sharding = NamedSharding(mesh, P(GLOBAL_AXIS))
    return jax.make_array_from_process_local_data(sharding, batch)


def _bucket_permutation(n, bucket_order):
    """Leaf traversal order for bucket formation: "forward" (leaf order),
    "reverse" (reverse leaf order — backward-availability order, since
    autodiff produces the LAST layer's gradients first), or an explicit
    permutation of range(n)."""
    if bucket_order is None or bucket_order == "forward":
        return list(range(n))
    if bucket_order == "reverse":
        return list(range(n - 1, -1, -1))
    if isinstance(bucket_order, str):
        raise ValueError(
            f"bucket_order must be 'forward', 'reverse', or an explicit "
            f"permutation sequence, got {bucket_order!r}")
    perm = [int(i) for i in bucket_order]
    if sorted(perm) != list(range(n)):
        raise ValueError(
            f"bucket_order permutation must rearrange range({n}) "
            f"exactly once each, got {perm}")
    return perm


def _buckets_by_nbytes(nbytes, threshold_bytes, bucket_order="forward"):
    """Greedy size-capped bucketing over per-item byte counts; buckets
    hold ORIGINAL indices, in `bucket_order` traversal order."""
    buckets = [[]]
    cur_bytes = 0
    for i in _bucket_permutation(len(nbytes), bucket_order):
        if buckets[-1] and cur_bytes + nbytes[i] > threshold_bytes:
            buckets.append([])
            cur_bytes = 0
        buckets[-1].append(i)
        cur_bytes += nbytes[i]
    return buckets


def _buckets_by_size(tensors, threshold_bytes, bucket_order="forward"):
    """Greedy size-capped bucket index lists (fusion-buffer analog).

    `bucket_order` picks the traversal: "reverse" forms the first bucket
    from the LAST leaves — the ones backward produces first — so its
    collective can issue while earlier layers' backward still runs
    (PyTorch-DDP bucket ordering)."""
    return _buckets_by_nbytes(
        [t.size * t.dtype.itemsize for t in tensors],
        threshold_bytes, bucket_order)


# -- straggler-reaction partition override ------------------------------
# The trace reaction policy (trace/reaction.py) rebalances the bucket
# partition away from a blamed rank by capping the bucket COUNT: fewer,
# larger buckets mean the straggler pays its per-collective overhead
# once per step instead of once per bucket.  Module-level so every
# partition consumer (allreduce_gradients, fused apply, ZeRO shard
# groups, zero3 placement) sees the same override, and generation-
# counted so compiled-program caches and fused optimizer state are
# loudly invalidated instead of silently diverging.
_REACTION = {"max_buckets": 0, "avoid_rank": -1, "generation": 0}


def set_reaction_rebalance(max_buckets: int, avoid_rank: int = -1) -> int:
    """Arm the straggler rebalance: cap the gradient bucket partition at
    `max_buckets` buckets (1 = one fused bucket, the strongest form).
    `avoid_rank` records WHO the rebalance shields — informational for
    metrics/tests; the partition itself is rank-symmetric so every rank
    must arm the same override in lockstep.  Returns the new reaction
    generation (part of the megastep autotune key, so armed/disarmed
    flips force a retrace; fused-apply state trips the loud re-init
    ValueError on the next update)."""
    _REACTION["max_buckets"] = max(0, int(max_buckets))
    _REACTION["avoid_rank"] = int(avoid_rank)
    _REACTION["generation"] += 1
    if _met.enabled():
        _met.reaction_max_buckets.set(_REACTION["max_buckets"])
    return _REACTION["generation"]


def clear_reaction_rebalance() -> int:
    """Disarm the straggler rebalance (also bumps the generation — the
    partition changes back, so the same loud-re-init rules apply)."""
    return set_reaction_rebalance(0, -1)


def reaction_rebalance():
    """(max_buckets, avoid_rank) of the armed override; (0, -1) when
    disarmed."""
    return (_REACTION["max_buckets"], _REACTION["avoid_rank"])


def reaction_generation() -> int:
    """Monotone counter bumped on every arm/disarm — joins the megastep
    autotune key next to the wire error-feedback generation."""
    return _REACTION["generation"]


def gradient_bucket_partition(
    leaves: Sequence[Any],
    compression=Compression.none,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
) -> list:
    """The bucket partition `allreduce_gradients` will use for `leaves`:
    a list of original-leaf-index lists, each covering every leaf exactly
    once, in collective-issue order.

    Shared by the per-bucket fused optimizer apply
    (parallel/optimizer.py) so init-time state partitioning and
    update-time reduction can never diverge.  Sizes are wire sizes
    (post-compression), computed via `jax.eval_shape` — no compute.
    For quantized wires the integer leaves (reduced exactly) form their
    own leading bucket.
    """
    from ..utils.autotune import (current_bucket_order,
                                  current_fusion_threshold,
                                  current_min_buckets)
    if fusion_threshold_bytes is None:
        fusion_threshold_bytes = current_fusion_threshold()
    if bucket_order is None:
        bucket_order = current_bucket_order()
    from ..ops.compression import _CooperativeCompressor
    _coop = (isinstance(compression, type)
             and issubclass(compression, _CooperativeCompressor))

    def _cap(nbytes):
        # The autotuner's per-bucket-count knob: force at least
        # `min_buckets` buckets by capping the effective threshold.
        m = current_min_buckets()
        cap = fusion_threshold_bytes
        if m > 1 and nbytes:
            cap = min(cap, max(1, -(-sum(nbytes) // m)))
        # Straggler-reaction override: at most `max_buckets` buckets by
        # RAISING the threshold (wins over both knobs above).  Exact for
        # max_buckets=1 — threshold >= total and the greedy split is
        # strict-`>`, so one bucket forms; best-effort for larger caps.
        mb = _REACTION["max_buckets"]
        if mb >= 1 and nbytes:
            cap = max(cap, -(-sum(nbytes) // mb))
        return cap

    if _coop:
        float_idx = [i for i, t in enumerate(leaves)
                     if jnp.issubdtype(t.dtype, jnp.floating)]
        int_idx = [i for i in range(len(leaves)) if i not in set(float_idx)]
        # Quantized ring rides a flat f32 staging buffer: 4 bytes/elem.
        nbytes = [leaves[i].size * 4 for i in float_idx]
        buckets = _buckets_by_nbytes(nbytes, _cap(nbytes), bucket_order)
        parts = [[float_idx[j] for j in b] for b in buckets if b]
        return ([int_idx] if int_idx else []) + parts
    nbytes = []
    for t in leaves:
        spec = jax.eval_shape(lambda x: compression.compress(x)[0], t)
        nbytes.append(spec.size * spec.dtype.itemsize)
    return [b for b in
            _buckets_by_nbytes(nbytes, _cap(nbytes), bucket_order) if b]


def shard_group_partition(
    leaves: Sequence[Any],
    compression=Compression.none,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
) -> list:
    """The ZeRO shard-group partition: the reduction buckets of
    `gradient_bucket_partition` split further by dtype (a flat shard
    buffer cannot mix dtypes).  Shared by
    `DistributedOptimizer(shard_optimizer_states=True)` state init /
    update AND the stage-3 `zero3_placement` so gradient shards,
    optimizer-state rows, and parameter rows all cover the same
    groups and can never diverge bit-for-bit."""
    groups = []
    for idxs in gradient_bucket_partition(
            leaves, compression=compression,
            fusion_threshold_bytes=fusion_threshold_bytes,
            bucket_order=bucket_order):
        by_dt = {}
        for i in idxs:
            by_dt.setdefault(jnp.result_type(leaves[i]), []).append(i)
        groups.extend(by_dt.values())
    return groups


def active_wire_policy(compression=Compression.none,
                       process_set: Optional[ProcessSet] = None):
    """The per-bucket wire policy the gradient reduction will apply, or
    None: HOROVOD_WIRE_POLICY engages only on the uncompressed global
    reduction (an explicit `compression=` always wins, and the
    cooperative ring spans the whole axis so process-set subsets stay
    exact), and "exact" deactivates it entirely — that path must stay
    bitwise-identical to the unwired pipeline."""
    if process_set is not None:
        return None
    if not (isinstance(compression, type)
            and issubclass(compression, NoneCompressor)):
        return None
    policy = _wire.policy_from_env()
    if policy is None or policy.exact:
        return None
    return policy


def wire_policy_plan(
    leaves: Sequence[Any],
    policy: Optional[_wire.WirePolicy] = None,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
) -> list:
    """The per-bucket wire assignment the policy produces for `leaves`:
    a list of `(indices, wire_name, raw_bytes, wire_bytes)` tuples over
    the same partition `reduce_gradient_buckets` uses (compression=none
    — the policy path).  `policy=None` reads HOROVOD_WIRE_POLICY; an
    inactive policy plans every bucket exact.  Pure bookkeeping (shapes
    and dtypes only) — usable from bench/tests without a mesh."""
    if policy is None:
        policy = _wire.policy_from_env() or _wire.WirePolicy()
    parts = gradient_bucket_partition(
        leaves, compression=Compression.none,
        fusion_threshold_bytes=fusion_threshold_bytes,
        bucket_order=bucket_order)
    plan = []
    for idxs in parts:
        all_float = all(jnp.issubdtype(leaves[i].dtype, jnp.floating)
                        for i in idxs)
        raw = sum(leaves[i].size * leaves[i].dtype.itemsize for i in idxs)
        name = policy.codec_for(raw, all_float)
        codec = _wire.get_codec(name)
        if codec.exact:
            wire_bytes = raw
        elif codec.cast_dtype is not None:
            wire_bytes = sum(
                leaves[i].size * jnp.dtype(codec.cast_dtype).itemsize
                for i in idxs)
        else:
            wire_bytes = codec.wire_nbytes(
                sum(leaves[i].size for i in idxs))
        plan.append((idxs, codec.name, raw, wire_bytes))
    return plan


def fused_pipeline_plan(
    leaves: Sequence[Any],
    policy: Optional[_wire.WirePolicy] = None,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
    chunk_bytes: Optional[int] = None,
) -> list:
    """The chunk schedule the fused pipeline would run for `leaves`: one
    `(indices, wire_name, n_chunks, chunk_bytes, occupancy)` tuple per
    bucket over the `wire_policy_plan` partition.  `occupancy` is the
    pipeline-overlap model 1 - 1/n_chunks — the fraction of a bucket's
    wire time that hides behind another chunk's stage (a 1-chunk bucket
    overlaps nothing; k chunks expose only the first chunk's latency).
    Pure bookkeeping — usable from bench/tests without a mesh."""
    from ..ops import fused_collectives as _fc
    if chunk_bytes is None:
        from ..utils.autotune import current_fused_chunk_bytes
        chunk_bytes = current_fused_chunk_bytes()
    plan = []
    for idxs, name, raw, _wb in wire_policy_plan(
            leaves, policy=policy,
            fusion_threshold_bytes=fusion_threshold_bytes,
            bucket_order=bucket_order):
        nelem = sum(leaves[i].size for i in idxs)
        itemsize = max((leaves[i].dtype.itemsize for i in idxs),
                       default=4)
        chunks = _fc.plan_chunks(nelem, itemsize, chunk_bytes=chunk_bytes)
        k = len(chunks)
        plan.append((idxs, name, k, chunk_bytes, 1.0 - 1.0 / k))
    return plan


def _sentinel_flags(
    leaves: Sequence[Any],
    results,
    axis_name: Optional[str],
    process_set: Optional[ProcessSet],
    input_buckets=(),
    sliced_inputs: bool = False,
) -> Any:
    """The fused non-finite sentinel: per-bucket 0/1 flags over the
    reduced OUTPUT leaves, OR-ed across ranks with one Max-allreduce so
    every rank keys the skip-step gate off the identical f32[B] vector.

    Exact and dtype-cast wires PROPAGATE non-finites (NaN+x=NaN,
    fp16 overflow goes to Inf), so the output check alone is complete
    for them — no pass over the inputs.  A quantizing codec's integer
    cast can launder NaN, so buckets riding one are listed in
    `input_buckets` (bucket positions, or True for all) and get the
    extra full pre-wire INPUT-leaf check.  `sliced_inputs` adds a 1/N
    sliced input scan to the remaining buckets: logically redundant,
    but scanning the inputs gives XLA's scheduler non-finite work that
    overlaps the collectives — the outputs-only program measured ~2x
    slower end-to-end on the CPU backend.  Cost: one scalar per
    bucket.  See docs/GUARD.md."""
    from ..guard import sentinel as _sent
    tl = _tl.get_timeline()
    flags = []
    for k, (idxs, outs) in enumerate(results):
        # The reduced outputs are replicated across the axis, so each
        # participant scans only its 1/N interleave; the Max-allreduce
        # below restores full coverage.
        f = _sent.sliced_nonfinite(outs, axis_name)
        if input_buckets is True or k in input_buckets:
            f = jnp.maximum(
                f, _sent.local_nonfinite([leaves[i] for i in idxs]))
        elif sliced_inputs:
            f = jnp.maximum(f, _sent.sliced_nonfinite(
                [leaves[i] for i in idxs], axis_name))
        flags.append(f)
        if tl is not None:
            tl.instant(f"guard_bucket_{k}", category="guard",
                       args={"bucket": k, "leaves": len(idxs)})
    vec = (jnp.stack(flags) if flags
           else jnp.zeros((1,), jnp.float32))
    return _sent.crossrank_or(vec, axis_name=axis_name,
                              process_set=process_set)


def reduce_gradient_buckets(
    leaves: Sequence[Any],
    op: C.ReduceOp = C.Average,
    compression=Compression.none,
    axis_name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
    error_feedback_leaves=None,
    sentinel: bool = False,
):
    """Reduce a flat gradient-leaf list bucket by bucket.

    Returns `(bucket_results, new_ef)`: `bucket_results` is a list of
    `(original_indices, reduced_leaves)` pairs in collective-issue order
    (the partition from `gradient_bucket_partition`), and `new_ef` is
    the updated per-float-leaf EF residual list in original float-leaf
    order (None unless `error_feedback_leaves` was passed).

    `sentinel=True` appends a third element: the cross-rank-agreed
    f32[B] per-bucket non-finite flag vector (`_sentinel_flags`),
    computed inside the same compiled program as the reduction.

    This is the single reduction engine behind `allreduce_gradients`
    (which reassembles the full tree) and the per-bucket fused optimizer
    apply (parallel/optimizer.py, which consumes each bucket the moment
    its reduction exists instead of barriering on all of them).

    When HOROVOD_WIRE_POLICY is set (and `compression` is none), each
    bucket rides the codec the policy picks for its byte size and dtype
    class — large all-float buckets at int8/int4 with optional error
    feedback, integer or small buckets exact (see docs/WIRE.md and
    `active_wire_policy`).
    """
    from ..ops import fused_collectives as _fc
    from ..ops.compression import _CooperativeCompressor
    _cooperative = (isinstance(compression, type) and
                    issubclass(compression, _CooperativeCompressor))
    # Fused computation-collective pipeline: in-jit only (the chunked
    # collectives need the mesh axis).  Read at trace time; the program
    # cache key carries the env so flipping it retraces.
    fused = _fc.fused_enabled() and axis_name is not None
    # Per-bucket wire policy: in-jit only (the cooperative ring needs
    # the mesh axis in scope; the eager path always reduces exactly).
    policy = (active_wire_policy(compression, process_set)
              if axis_name is not None else None)
    if error_feedback_leaves is not None and not (_cooperative
                                                  or policy is not None):
        raise ValueError(
            "error_feedback_state only applies to the quantized wire "
            "formats (Compression.int8 / int4 / fp8_*, or a quantizing "
            "HOROVOD_WIRE_POLICY) — exact and fp16/bf16 wires have no "
            "compression error to feed back")
    parts = gradient_bucket_partition(
        leaves, compression=compression,
        fusion_threshold_bytes=fusion_threshold_bytes,
        bucket_order=bucket_order)
    if _met.enabled():
        raw = sum(l.size * l.dtype.itemsize for l in leaves
                  if hasattr(l, "size") and hasattr(l, "dtype"))
        _met.buckets_per_step.set(len(parts))
        _met.bucket_bytes.set(raw // max(1, len(parts)))
    if _cooperative:
        wire = compression.wire
        # Cooperative wire format: the quantized ring allreduce IS the
        # collective (ops/quantized.py).  In-jit only — it needs the
        # mesh axis in scope.
        if axis_name is None:
            raise ValueError(
                f"Compression.{wire} requires the in-jit path (axis_name;"
                " e.g. inside hvd.data_parallel) — the quantized ring "
                "collective needs the mesh axis in scope")
        if process_set is not None:
            raise ValueError(
                f"Compression.{wire} does not support process_set "
                "subsets; use fp16/bf16 compression for subset "
                "reductions")
        if op not in (C.Average, C.Sum):
            raise ValueError(
                f"Compression.{wire} supports op=Average or Sum, got {op}")
        from ..ops.quantized import quantized_allreduce_shard

        # Quantized wire is float-only: integer leaves (step counters
        # etc.) must keep summing exactly, same as hierarchical.py's
        # DCN-wire filter — the partition routes them into their own
        # leading bucket on the exact grouped path.
        float_ord = {}
        for i, t in enumerate(leaves):
            if jnp.issubdtype(t.dtype, jnp.floating):
                float_ord[i] = len(float_ord)
        if error_feedback_leaves is not None and \
                len(error_feedback_leaves) != len(float_ord):
            raise ValueError(
                f"error_feedback_state has {len(error_feedback_leaves)} "
                f"leaves; expected one per float gradient leaf "
                f"({len(float_ord)}) — build it with "
                f"error_feedback_init(grads)")
        new_ef = [None] * len(float_ord)
        results = []
        for idxs in parts:
            if idxs and idxs[0] not in float_ord:
                exact = C.grouped_allreduce(
                    [leaves[i] for i in idxs], op=op, axis_name=axis_name)
                results.append((idxs, list(exact)))
                continue
            flat = jnp.concatenate(
                [leaves[i].astype(jnp.float32).reshape(-1) for i in idxs])
            if error_feedback_leaves is not None:
                # Sender-side EF inside the ring: the collective adds
                # the residual, captures every wire encode's error at
                # its sender, and hands the new residual back — the
                # dropped bits telescope exactly across steps (see
                # quantized_allreduce_shard).
                ef_flat = jnp.concatenate(
                    [error_feedback_leaves[float_ord[i]].reshape(-1)
                     for i in idxs])
                if fused:
                    reduced, err = _fc.pipelined_allreduce_shard(
                        flat, axis_name, average=(op is C.Average),
                        wire=wire, error_feedback=ef_flat)
                else:
                    reduced, err = quantized_allreduce_shard(
                        flat, axis_name, average=(op is C.Average),
                        wire=wire, error_feedback=ef_flat)
            elif fused:
                reduced = _fc.pipelined_allreduce_shard(
                    flat, axis_name, average=(op is C.Average), wire=wire)
            else:
                reduced = quantized_allreduce_shard(
                    flat, axis_name, average=(op is C.Average), wire=wire)
            outs = []
            offset = 0
            for i in idxs:
                n = leaves[i].size
                outs.append(reduced[offset:offset + n]
                            .reshape(leaves[i].shape)
                            .astype(leaves[i].dtype))
                if error_feedback_leaves is not None:
                    new_ef[float_ord[i]] = err[offset:offset + n].reshape(
                        leaves[i].shape)
                offset += n
            results.append((idxs, outs))
        ef_out = (new_ef if error_feedback_leaves is not None else None)
        if sentinel:
            # Every float bucket rode the quantized ring: input checks on.
            return results, ef_out, _sentinel_flags(
                leaves, results, axis_name, process_set,
                input_buckets=True)
        return results, ef_out
    if policy is not None:
        if op not in (C.Average, C.Sum):
            raise ValueError(
                f"HOROVOD_WIRE_POLICY supports op=Average or Sum, got "
                f"{op}; unset the policy for other reductions")
        from ..ops.quantized import quantized_allreduce_shard

        float_ord = {}
        for i, t in enumerate(leaves):
            if jnp.issubdtype(t.dtype, jnp.floating):
                float_ord[i] = len(float_ord)
        if error_feedback_leaves is not None and \
                len(error_feedback_leaves) != len(float_ord):
            raise ValueError(
                f"error_feedback_state has {len(error_feedback_leaves)} "
                f"leaves; expected one per float gradient leaf "
                f"({len(float_ord)}) — build it with "
                f"error_feedback_init(grads)")
        # Exact/cast buckets drop nothing — their residuals pass through
        # unchanged (zeros stay zeros); cooperative buckets overwrite
        # their entries below.
        new_ef = (list(error_feedback_leaves)
                  if error_feedback_leaves is not None else None)
        tl = _tl.get_timeline()
        traced = any(isinstance(l, jax.core.Tracer) for l in leaves)
        results = []
        raw_total = wire_total = 0
        fmt_bytes: dict = {}
        launder_buckets = set()  # rode a NaN-laundering quantized codec
        for k, idxs in enumerate(parts):
            all_float = all(i in float_ord for i in idxs)
            raw = sum(leaves[i].size * leaves[i].dtype.itemsize
                      for i in idxs)
            codec = _wire.get_codec(policy.codec_for(raw, all_float))
            nelem = sum(leaves[i].size for i in idxs)
            if not codec.exact and codec.cast_dtype is None:
                launder_buckets.add(k)
            if codec.exact:
                wbytes = raw
                group = [leaves[i] for i in idxs]
                # pipelined_grouped_allreduce is bitwise-equal to the
                # unfused grouped collective (psum is elementwise), so
                # the fused exact path keeps the exact-wire contract.
                outs = list(
                    _fc.pipelined_grouped_allreduce(
                        group, op=op, axis_name=axis_name) if fused
                    else C.grouped_allreduce(
                        group, op=op, axis_name=axis_name))
            elif codec.cast_dtype is not None:
                wbytes = nelem * jnp.dtype(codec.cast_dtype).itemsize
                group = [leaves[i].astype(codec.cast_dtype) for i in idxs]
                reduced = (
                    _fc.pipelined_grouped_allreduce(
                        group, op=op, axis_name=axis_name) if fused
                    else C.grouped_allreduce(
                        group, op=op, axis_name=axis_name))
                outs = [r.astype(leaves[i].dtype)
                        for i, r in zip(idxs, reduced)]
            else:
                wbytes = codec.wire_nbytes(nelem)
                flat = jnp.concatenate(
                    [leaves[i].astype(jnp.float32).reshape(-1)
                     for i in idxs])
                if error_feedback_leaves is not None:
                    ef_flat = jnp.concatenate(
                        [error_feedback_leaves[float_ord[i]].reshape(-1)
                         for i in idxs])
                    if fused:
                        reduced, err = _fc.pipelined_allreduce_shard(
                            flat, axis_name, average=(op is C.Average),
                            wire=codec.name, error_feedback=ef_flat)
                    else:
                        reduced, err = quantized_allreduce_shard(
                            flat, axis_name, average=(op is C.Average),
                            wire=codec.name, error_feedback=ef_flat)
                elif fused:
                    reduced = _fc.pipelined_allreduce_shard(
                        flat, axis_name, average=(op is C.Average),
                        wire=codec.name)
                else:
                    reduced = quantized_allreduce_shard(
                        flat, axis_name, average=(op is C.Average),
                        wire=codec.name)
                outs = []
                offset = 0
                for i in idxs:
                    n = leaves[i].size
                    outs.append(reduced[offset:offset + n]
                                .reshape(leaves[i].shape)
                                .astype(leaves[i].dtype))
                    if error_feedback_leaves is not None:
                        new_ef[float_ord[i]] = err[offset:offset + n] \
                            .reshape(leaves[i].shape)
                    offset += n
            raw_total += raw
            wire_total += wbytes
            fmt_bytes[codec.name] = fmt_bytes.get(codec.name, 0) + wbytes
            if tl is not None:
                # Host-side per-bucket wire label — once per compile for
                # traced steps, matching the trace-time gauge idiom.
                tl.instant(f"wire_bucket_{k}", category="wire",
                           args={"bucket": k, "format": codec.name,
                                 "leaves": len(idxs), "raw_bytes": raw,
                                 "wire_bytes": wbytes})
                if fused:
                    cb = _fc.plan_chunks(nelem, 4)
                    tl.instant(f"fused_bucket_{k}", category="fused",
                               args={"bucket": k, "format": codec.name,
                                     "chunks": len(cb),
                                     "chunk_bytes": 4 * cb[0][1]})
            results.append((idxs, outs))
        if _met.enabled():
            if traced:
                # Static per-step savings, recorded at trace time like
                # hvd_grad_bytes_per_step (counting here per call would
                # count compiles, not steps).
                _met.wire_bytes_saved_per_step.set(raw_total - wire_total)
                for fmt, b in fmt_bytes.items():
                    _met.wire_format_bytes.labels(fmt).set(b)
                if fused:
                    from ..utils.autotune import current_fused_chunk_bytes
                    _met.fused_chunk_bytes.set(current_fused_chunk_bytes())
            else:
                _met.wire_bytes_saved.inc(raw_total - wire_total)
        if sentinel:
            return results, new_ef, _sentinel_flags(
                leaves, results, axis_name, process_set,
                input_buckets=launder_buckets, sliced_inputs=True)
        return results, new_ef
    compressed, ctxs = [], []
    for leaf in leaves:
        c, ctx = compression.compress(leaf)
        compressed.append(c)
        ctxs.append(ctx)
    # Greedy size-capped buckets (fusion threshold analog); dtype grouping
    # within a bucket is grouped_allreduce's job.
    results = []
    for idxs in parts:
        group = [compressed[i] for i in idxs]
        if fused and process_set is None and op in (C.Average, C.Sum):
            # process-set subsets keep the unfused grouped collective —
            # the chunked path has no subset plumbing.
            reduced = _fc.pipelined_grouped_allreduce(
                group, op=op, axis_name=axis_name)
        else:
            reduced = C.grouped_allreduce(
                group, op=op, axis_name=axis_name,
                process_set=process_set)
        results.append(
            (idxs, [compression.decompress(r, ctxs[i])
                    for i, r in zip(idxs, reduced)]))
    if sentinel:
        return results, None, _sentinel_flags(
            leaves, results, axis_name, process_set, sliced_inputs=True)
    return results, None


def allreduce_gradients(
    grads: Any,
    op: C.ReduceOp = C.Average,
    compression=Compression.none,
    axis_name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
    fusion_threshold_bytes: Optional[int] = None,
    bucket_order=None,
    error_feedback_state: Any = None,
    sentinel: bool = False,
) -> Any:
    """Average a gradient pytree across ranks with wire compression and
    fusion-buffer-style bucketing (reference: FusionBufferManager — here
    bucketing is concatenation in the traced graph; multiple buckets let
    XLA overlap collectives with remaining backward compute).

    `fusion_threshold_bytes` defaults to HOROVOD_FUSION_THRESHOLD (64 MB,
    the reference default), overridden live by the autotuner when
    HOROVOD_AUTOTUNE=1.

    `bucket_order` picks the bucket-formation traversal — "forward",
    "reverse" (the default, via HOROVOD_BUCKET_ORDER / the autotuner),
    or an explicit permutation of the leaf indices.  Reverse is
    backward-availability order: the first bucket holds the LAST
    layers' gradients — the ones autodiff produces first — so its
    collective can issue while earlier layers' backward still runs
    (PyTorch-DDP bucket ordering).  Exact and fp16/bf16 wires are
    bitwise order-invariant (bucketing never mixes elements across
    leaves); quantized wires shift chunk-scale boundaries, so results
    across orders agree only to wire tolerance.

    `error_feedback_state` (quantized wires only; create with
    `error_feedback_init(grads)`): standard EF compression — each rank
    adds its carried residual to the gradient before encoding and keeps
    the new LOCAL encode error for the next step, so the per-step
    quantization bias telescopes away (time-averaged error O(1/t)
    instead of a persistent bias).  When passed, the return value is
    `(reduced, new_error_feedback_state)`; thread the state through
    your step like optimizer state.

    `sentinel=True` additionally returns the cross-rank per-bucket
    non-finite flag vector (f32[B]) as the LAST element — `reduced` /
    `(reduced, flags)` / `(reduced, new_ef, flags)` depending on which
    options are on (see docs/GUARD.md)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        out = [grads]
        if error_feedback_state is not None:
            out.append(error_feedback_state)
        if sentinel:
            out.append(jnp.zeros((1,), jnp.float32))
        return tuple(out) if len(out) > 1 else out[0]
    if _met.enabled():
        nbytes = sum(l.size * l.dtype.itemsize for l in leaves
                     if hasattr(l, "size") and hasattr(l, "dtype"))
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            # Trace time — this branch fires once per compile, not per
            # step: record the static per-step payload (multiply by
            # hvd_steps_total for in-jit traffic).  Incrementing a
            # counter here would silently count compiles, not steps.
            _met.grad_bytes_per_step.set(nbytes)
        else:
            _met.grad_bytes_reduced.inc(nbytes)
    ef_leaves = ef_def = None
    if error_feedback_state is not None:
        ef_leaves, ef_def = jax.tree_util.tree_flatten(error_feedback_state)
    red = reduce_gradient_buckets(
        leaves, op=op, compression=compression, axis_name=axis_name,
        process_set=process_set,
        fusion_threshold_bytes=fusion_threshold_bytes,
        bucket_order=bucket_order, error_feedback_leaves=ef_leaves,
        sentinel=sentinel)
    if sentinel:
        results, new_ef, flags = red
    else:
        results, new_ef = red
    out = [None] * len(leaves)
    for idxs, reduced in results:
        for i, r in zip(idxs, reduced):
            out[i] = r
    result = jax.tree_util.tree_unflatten(treedef, out)
    ret = [result]
    if error_feedback_state is not None:
        ret.append(jax.tree_util.tree_unflatten(ef_def, new_ef))
    if sentinel:
        ret.append(flags)
    return tuple(ret) if len(ret) > 1 else result


def error_feedback_init(grads: Any):
    """Zero EF residuals for `allreduce_gradients(...,
    error_feedback_state=...)`: one f32 zero array per FLOAT leaf of
    `grads`, in leaf order (integer leaves ride the exact wire and
    carry no residual)."""
    leaves, _ = jax.tree_util.tree_flatten(grads)
    return [jnp.zeros(leaf.shape, jnp.float32) for leaf in leaves
            if jnp.issubdtype(leaf.dtype, jnp.floating)]


def distributed_grad(
    loss_fn: Callable,
    argnums=0,
    has_aux: bool = False,
    op: C.ReduceOp = C.Average,
    compression=Compression.none,
    axis_name: Optional[str] = None,
    process_set: Optional[ProcessSet] = None,
):
    """`jax.value_and_grad` + cross-rank gradient averaging — the
    functional form of DistributedGradientTape."""
    vg = jax.value_and_grad(loss_fn, argnums=argnums, has_aux=has_aux)

    @functools.wraps(loss_fn)
    def wrapped(*args, **kwargs):
        val, grads = vg(*args, **kwargs)
        grads = allreduce_gradients(
            grads, op=op, compression=compression, axis_name=axis_name,
            process_set=process_set,
        )
        return val, grads

    return wrapped


class DistributedGradientTape:
    """Imperative-looking facade matching `hvd.DistributedGradientTape`
    (reference: horovod/tensorflow/__init__.py).

        tape = hvd.DistributedGradientTape()
        loss, grads = tape.gradient(loss_fn, params, batch)
    """

    def __init__(self, op: C.ReduceOp = C.Average,
                 compression=Compression.none,
                 axis_name: Optional[str] = None,
                 process_set: Optional[ProcessSet] = None):
        self._op = op
        self._compression = compression
        self._axis_name = axis_name
        self._process_set = process_set

    def gradient(self, loss_fn: Callable, params, *args, **kwargs):
        g = distributed_grad(
            loss_fn, op=self._op, compression=self._compression,
            axis_name=self._axis_name, process_set=self._process_set,
        )
        return g(params, *args, **kwargs)


def data_parallel(
    step_fn: Callable,
    mesh: Optional[Mesh] = None,
    axis_name: str = GLOBAL_AXIS,
    batch_args: Sequence[int] = (2,),
    donate_args: Sequence[int] = (0, 1),
    static_args: Sequence[int] = (),
    arg_specs: Optional[dict] = None,
    out_specs: Any = None,
):
    """Compile a per-rank `step_fn(params, opt_state, batch, ...)` into one
    SPMD program over the mesh.

    - positional args in `batch_args` are sharded on dim 0 over `axis_name`
    - everything else is replicated
    - args in `donate_args` are donated (weights update in-place in HBM)
    - `arg_specs` maps an arg position to an explicit PartitionSpec pytree
      (structure matching that argument), overriding the batch/replicated
      default — e.g. `{1: hvd.sharded_state_specs(opt_state)}` places a
      ZeRO-1 optimizer state's (n_ranks, shard) rows on their owner
      ranks instead of replicating them (docs/SHARDED_OPTIMIZER.md)
    - `out_specs` is the shard_map out_specs pytree (default P(),
      fully replicated outputs); pass the matching spec tree when the
      step returns mesh-sharded state

    Inside `step_fn`, cross-rank reduction is explicit —
    `hvd.allreduce(grads)` / `DistributedOptimizer` — mirroring the
    reference's explicit allreduce, but compiled into the step so XLA
    overlaps it with backward compute.
    """
    mesh = mesh or basics.global_mesh()
    arg_specs = dict(arg_specs or {})
    out_spec = P() if out_specs is None else out_specs

    def _spec_for(i):
        if i in arg_specs:
            return arg_specs[i]
        return P(axis_name) if i in batch_args else P()

    if static_args:
        # Static args preclude per-arg in_shardings; legacy wrapper path.
        def wrapper(*args):
            n_args = len(args)
            in_specs = tuple(_spec_for(i) for i in range(n_args))
            sm = shard_map(
                step_fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_spec, check_vma=False,
            )
            return sm(*args)

        return jax.jit(wrapper, donate_argnums=tuple(donate_args),
                       static_argnums=tuple(static_args))

    # Explicit in_shardings so the FIRST compile is already steady-state.
    # Without them, jit infers input layouts from whatever the caller
    # passes (host-committed arrays), while the step's outputs come back
    # as NamedSharding over the mesh — the next call would then see
    # different input shardings and silently recompile the whole program
    # (observed: an extra full ResNet-50 compile inside the timed loop).
    #
    # The cache key includes every live autotuner knob (fusion
    # threshold, bucket order, min buckets): the bucketing inside the
    # traced step bakes the values read at trace time, so when
    # HOROVOD_AUTOTUNE proposes a new configuration the step must
    # retrace to actually change the bucket structure (reference:
    # parameter_manager.cc re-tunes the running job's fusion buffer).
    compiled_cache = {}

    def _autotune_key():
        from ..utils import autotune as _at
        # The wire policy is read from the environment at trace time, so
        # a spec change (tests/operators flipping HOROVOD_WIRE_POLICY
        # between steps) must retrace just like a knob proposal.
        wire_spec = util.getenv("WIRE_POLICY")
        # Trace-time envs the bucketing bakes in: the auto policy's big
        # format and the fused pipeline's on/off + chunk size all change
        # the traced program, so a flip between steps must retrace (the
        # knob-tuned values ride pm.values() below; these cover the
        # env-only case with no tuner attached).
        # The wire error-feedback generation joins the key so a
        # reset_error_feedback() (elastic reset, guard rollback) forces
        # a retrace: the sharded-optimizer EF path bakes the generation
        # it saw at trace time and zeroes any residual stamped with an
        # older one — without the retrace the stale residual would
        # bleed its pre-recovery correction into the first new step.
        # Generation 0 maps to None so the no-envs fast path survives.
        env_part = (wire_spec, util.getenv("WIRE_BIG_FORMAT"),
                    util.getenv("FUSED_COLLECTIVES"),
                    util.getenv("FUSED_CHUNK_BYTES"),
                    util.getenv("ZERO_STAGE"),
                    util.getenv("ZERO_GATHER_WIRE"),
                    _wire.error_feedback_generation() or None,
                    # Straggler-reaction arm/disarm changes the bucket
                    # partition the traced program baked in.
                    reaction_generation() or None)
        pm = _at.get_manager()
        if pm is None:
            return env_part if any(env_part) else None
        # ALL live knob values (fusion threshold, bucket order, min
        # buckets, ...): any proposal the tuner applies must force a
        # retrace, or the step keeps running the old bucketing.
        return (env_part, tuple(pm.values().items()))

    def _autotune_record(args):
        from ..utils import autotune as _at
        pm = _at.get_manager()
        if pm is None:
            return
        items = 1
        if batch_args and batch_args[0] < len(args):
            leaves = jax.tree_util.tree_leaves(args[batch_args[0]])
            if leaves and hasattr(leaves[0], "shape") and leaves[0].shape:
                items = int(leaves[0].shape[0])
        pm.record_step(items)

    def _coerce(x, sharding):
        # jit with explicit in_shardings REJECTS committed arrays whose
        # sharding differs (rather than resharding); accept them the way
        # plain jit would, with an explicit reshard.  Steady state (the
        # training loop feeding outputs back in) matches and pays only a
        # per-leaf comparison.
        if isinstance(x, jax.Array) and not x.is_deleted() \
                and not x.sharding.is_equivalent_to(sharding, x.ndim):
            return jax.device_put(x, sharding)
        return x

    # Per-step host spans: `hvd.step.step` in a profiler trace, beside
    # the device's operations, and for the fleet tracer (docs/TRACE.md)
    # one `ph="X"` record per dispatched step in a HOROVOD_TIMELINE
    # file, carrying the step ID the cross-rank merger aligns on.  Gate
    # exists so a timeline run can drop back to instants-only.
    step_span = ((lambda: _tl.span("step", "step"))
                 if util.env_bool("TRACE_STEP_SPANS", True)
                 else contextlib.nullcontext)

    def call(*args):
        n_args = len(args)
        key = (n_args, _autotune_key())
        entry = compiled_cache.get(key)
        if entry is None:
            in_specs = tuple(_spec_for(i) for i in range(n_args))
            sm = shard_map(
                step_fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_spec, check_vma=False,
            )
            in_shardings = tuple(
                jax.tree_util.tree_map(
                    lambda p: NamedSharding(mesh, p), _spec_for(i),
                    is_leaf=lambda x: isinstance(x, P))
                for i in range(n_args)
            )
            fn = jax.jit(
                sm, in_shardings=in_shardings,
                donate_argnums=tuple(d for d in donate_args if d < n_args),
            )
            entry = (fn, in_shardings)
            # Only the current threshold's program will ever run again:
            # evict superseded-threshold entries so a long autotune run
            # does not accumulate one full compiled step per proposal.
            for k in [k for k in compiled_cache
                      if k[0] == n_args and k[1] != key[1]]:
                del compiled_cache[k]
            compiled_cache[key] = entry
        fn, in_shardings = entry
        t0 = time.perf_counter()
        # The timeline's `step` event is written on leaving, so after
        # mark_cycle: it carries the ID of the step it measured (step N
        # ends at CYCLE_N).
        with step_span():
            args = tuple(
                (jax.tree_util.tree_map(lambda x, s=s: _coerce(x, s), a)
                 if isinstance(s, NamedSharding)
                 # arg_specs entry: a sharding tree mirroring the arg's
                 # own structure, so pair the two trees leaf-by-leaf.
                 else jax.tree_util.tree_map(_coerce, a, s))
                for a, s in zip(args, in_shardings)
            )
            out = fn(*args)
            # Feed the autotuner (HOROVOD_AUTOTUNE=1): one throughput
            # sample per steps_per_sample invocations drives the GP/EI
            # proposal loop (reference: parameter_manager.cc fed from the
            # runtime, not by user code).
            _autotune_record(args)
            # Step-cycle marker (reference: HOROVOD_TIMELINE_MARK_CYCLES
            # marks each runloop cycle; the SPMD analog is one compiled
            # step).
            tl = _tl.get_timeline()
            if tl is not None:
                tl.mark_cycle()
        if _met.enabled():
            _met.steps.inc()
            # Host-side wall time of this step's dispatch; the fleet view
            # reads it per rank, and offline trace analysis overwrites it
            # with the cross-rank critical path (docs/TRACE.md).
            _met.critical_path_ms.set((time.perf_counter() - t0) * 1e3)
            from ..ops.fused_collectives import fused_enabled
            if fused_enabled():
                _met.fused_steps.inc()
        return out

    return call
