"""Headline benchmark: ResNet-50 synthetic data, img/sec per chip.

Mirrors the reference's `examples/pytorch/pytorch_synthetic_benchmark.py`
(SURVEY.md §6, BASELINE.json metric "ResNet-50 img/sec/chip"): synthetic
images, SGD-momentum, train-mode batch norm, warmup then timed iterations.

TPU-first differences from the reference harness:
  - one compiled SPMD step (gradient allreduce fused into the step program)
    instead of eager grad hooks + background negotiation;
  - bf16 compute / f32 params;
  - input donation so weights update in place in HBM.

`python bench.py` measures on the TPU or exits non-zero: there is no
CPU mode of the headline path, no retry and no replay of an earlier
record.  All diagnostics go to stderr; the record is ONE JSON line on
stdout carrying the device it ran on (`platform`, `device_kind`,
`device_count`).

Reported fields:
  value        — img/sec/chip of the framework's distributed step
  vs_baseline  — framework vs raw-JAX on identical work (1.0 = zero
                 framework overhead on one chip; >1.0 = fusion wins)

`python bench.py --cpu-reports` runs the chip-independent reports on the
CPU host mesh instead (simulated 8-device scaling efficiency, ZeRO byte
accounting, live-reshard timing) — counts and CPU wall-clock, never a
device metric.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

# Freshness window for comparing against the previous record of the
# --chaos/--autoscale/--obs files: beyond it the previous record is
# marked stale and not compared against.
CACHE_MAX_AGE_H = float(
    os.environ.get("HOROVOD_BENCH_CACHE_MAX_AGE_H", "24"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# The measured step (shared by main bench and the sim-scaling child)
# ---------------------------------------------------------------------------

def build_step(opt, cfg, distributed: bool,
               reduce_grads_in_step: bool = True):
    """The measured train step.  `reduce_grads_in_step=False` leaves the
    gradient allreduce to `opt` itself (hvd.DistributedOptimizer with
    fused_apply: per-bucket reduce + apply chains instead of an
    allreduce barrier before one global update — the overlap-aware
    pipeline, the sim-scaling default)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import resnet_apply
    import horovod_tpu as hvd

    def step(state, opt_state, batch):
        x, y = batch

        def loss_fn(p):
            # Batch-norm stats are LOCAL per worker — reference parity:
            # Horovod's benchmark models use plain BatchNorm; cross-rank
            # SyncBatchNormalization is opt-in (sync_batch_norm.py).
            # Syncing here costs ~2 tiny collectives per BN layer per
            # pass and is what sank scaling_eff_sim8 to 0.85 in r02 (see
            # docs/PERF_NOTES.md).
            logits, ns = resnet_apply(
                {"params": p, "batch_stats": state["batch_stats"],
                 "config": cfg},
                x, train=True, compute_dtype=jnp.bfloat16,
                axis_name=None)
            onehot = jax.nn.one_hot(y, logits.shape[-1])
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
            return loss, ns

        (loss, ns), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        if distributed:
            if reduce_grads_in_step:
                grads = hvd.allreduce(grads)
            # Stats computed per-shard must be re-replicated before the
            # step returns them under out_specs=P(): ONE fused pmean of
            # the whole batch_stats tree (vs r02's 2 collectives per BN
            # layer at apply time — see docs/PERF_NOTES.md).
            ns = hvd.allreduce(ns)
        updates, new_opt = opt.update(grads, opt_state, state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "batch_stats": ns}, new_opt, loss

    return step


def time_steps(compiled, state, opt_state, batch, warmup, iters):
    import jax

    for _ in range(warmup):
        state, opt_state, loss = compiled(state, opt_state, batch)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, opt_state, loss = compiled(state, opt_state, batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return dt / iters, state, opt_state


# ---------------------------------------------------------------------------
# Simulated scaling efficiency child (ResNet-18 on an n-device CPU mesh)
# ---------------------------------------------------------------------------

def run_sim_child(n_devices: int, distributed: bool = True) -> None:
    """Child mode: per-chip img/sec of the framework DP step on an
    n-device virtual CPU mesh.  Prints one JSON line.

    distributed=False runs the identical compute WITHOUT the gradient
    allreduce — the compute-only baseline that isolates per-step
    collective time (reference: the timeline's NEGOTIATE/NCCL phases vs
    compute)."""
    from horovod_tpu.common.util import force_cpu_platform
    force_cpu_platform(n_devices)
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet_init

    hvd.init()
    assert hvd.size() == n_devices
    # Per-chip batch 16: at 8 the fixed gradient-psum cost (ResNet-18's
    # 11M params move regardless of batch) dominates the tiny compute
    # slice and the shared-core measurement wobbles around the target;
    # 16 keeps the compute:collective ratio representative of real
    # configs (per-chip 64-256 on hardware).
    per_chip = 16
    batch = per_chip * n_devices
    v = resnet_init(jax.random.PRNGKey(0), 18, num_classes=100)
    base_opt = optax.sgd(0.01, momentum=0.9)
    # Default pipeline: reverse-availability bucketing + per-bucket fused
    # optimizer apply (hvd.DistributedOptimizer handles the reduction).
    # HOROVOD_BENCH_LEGACY_PIPELINE=1 restores the r05 barriered path
    # (one allreduce of the whole tree, then one global opt.update) for
    # before/after comparison.
    legacy = os.environ.get("HOROVOD_BENCH_LEGACY_PIPELINE") == "1"
    sharded = os.environ.get("HOROVOD_SHARD_OPTIMIZER") == "1"
    quant = bool(os.environ.get("HOROVOD_WIRE_POLICY"))
    guard = os.environ.get("HOROVOD_GUARD") == "1"
    fusedc = os.environ.get("HOROVOD_FUSED_COLLECTIVES") == "1"
    if legacy or not distributed:
        pipeline = "legacy"
    elif sharded:
        pipeline = "sharded"
    elif quant:
        # Overlap pipeline + per-bucket wire policy (docs/WIRE.md): big
        # buckets ride the quantized ring, small stay exact.
        pipeline = "quant"
    elif guard:
        # Overlap pipeline + fused non-finite sentinel (docs/GUARD.md):
        # HOROVOD_GUARD=1 arms the skip-step gate inside the
        # DistributedOptimizer; the delta vs "overlap" is the sentinel
        # cost (one scalar per bucket + one tiny Max-allreduce).
        pipeline = "guard"
    elif fusedc:
        # Overlap pipeline + chunked fused computation-collective
        # pipeline (docs/FUSED_COLLECTIVES.md): each bucket's reduction
        # runs as fused_chunk_bytes chunks whose collectives issue while
        # the rest of the bucket packs; the delta vs "overlap" is the
        # intra-bucket wire time the chunking hides (or the chunking
        # overhead, when negative).
        pipeline = "fused"
    else:
        pipeline = "overlap"
    if pipeline == "sharded":
        # ZeRO-1: reduce-scatter the bucketed grads, update the local
        # optimizer-state shard, allgather params (docs/SHARDED_OPTIMIZER.md).
        opt = hvd.DistributedOptimizer(base_opt, shard_optimizer_states=True)
        step_fn = build_step(opt, v["config"], distributed=True,
                             reduce_grads_in_step=False)
    elif pipeline in ("overlap", "quant", "guard", "fused"):
        opt = hvd.DistributedOptimizer(base_opt, fused_apply=True)
        step_fn = build_step(opt, v["config"], distributed=True,
                             reduce_grads_in_step=False)
    else:
        opt = base_opt
        step_fn = build_step(opt, v["config"], distributed=distributed)
    state = {"params": v["params"], "batch_stats": v["batch_stats"]}
    opt_state = opt.init(state["params"])
    # Per-chip resident inner optimizer-state bytes — the ZeRO-1
    # denominator (shrinks ~n_devices-fold under the sharded pipeline).
    opt_state_bytes = hvd.optimizer_state_bytes(opt_state)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, 3),
                          jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 100)

    step = hvd.data_parallel(step_fn)
    sb = hvd.shard_batch((x, y))
    # More iters at n=1: its ~0.4s steps carry most of the efficiency
    # ratio's run-to-run noise on the shared core.
    iters = 12 if n_devices == 1 else 6
    t, _, _ = time_steps(step, state, opt_state, sb, warmup=2, iters=iters)
    record = {"n": n_devices, "step_time_s": t,
              "pipeline": pipeline,
              "opt_state_bytes": opt_state_bytes,
              "per_chip_img_sec": batch / t / n_devices}
    if pipeline == "quant":
        # Static per-step wire-byte accounting of the active policy over
        # the gradient leaves (same bookkeeping hvd_wire_bytes_saved
        # reports; grads share the param tree's shapes).
        plan = hvd.wire_policy_plan(
            jax.tree_util.tree_leaves(state["params"]))
        record["wire_bytes_saved"] = sum(
            raw - wb for _, _, raw, wb in plan)
        record["wire_bytes_raw"] = sum(raw for _, _, raw, _ in plan)
    if pipeline == "fused":
        # Static per-chunk pipeline schedule over the gradient leaves:
        # chunk counts and the occupancy model (1 - 1/k per bucket —
        # the fraction of a bucket's wire time another chunk's stage
        # covers).  Same bookkeeping the fused_bucket_k timeline
        # instants carry.
        fplan = hvd.fused_pipeline_plan(
            jax.tree_util.tree_leaves(state["params"]))
        ks = [k for _, _, k, _, _ in fplan]
        record["fused_buckets"] = len(fplan)
        record["fused_chunks_total"] = int(sum(ks))
        record["fused_chunk_bytes"] = int(fplan[0][3]) if fplan else 0
        record["fused_occupancy_mean"] = round(
            sum(occ for *_, occ in fplan) / max(1, len(fplan)), 4)
        record["fused_occupancy_max"] = round(
            max((occ for *_, occ in fplan), default=0.0), 4)
    from horovod_tpu.utils import timeline as _tl_mod
    if _tl_mod.get_timeline() is not None:
        # Trace-measured pass (docs/TRACE.md): restart the timeline so
        # the file holds ONLY device-synced steps — the async warmup/
        # timing dispatches above would otherwise pollute the cycle
        # windows `trace analyze` measures — then run per-step-synced
        # iterations; data_parallel marks one CYCLE_n per call.
        trace_iters = 6
        hvd.start_timeline(os.environ["HOROVOD_TIMELINE"],
                           mark_cycles=True)
        for _ in range(trace_iters):
            state, opt_state, loss = step(state, opt_state, sb)
            jax.block_until_ready(loss)
        hvd.stop_timeline()
        record["trace_steps"] = trace_iters
    print(json.dumps(record))


def run_zero_bytes_child(n_devices: int) -> None:
    """Child mode: ZeRO ladder memory accounting on an n-device virtual
    CPU mesh — per-chip resident bytes of the gradient accumulator
    (stage 1 vs stage 2, backward_passes_per_step=2) and of the
    parameters (replicated vs stage-3 at-rest shards).  Prints one JSON
    line (docs/SHARDED_OPTIMIZER.md memory model)."""
    from horovod_tpu.common.util import force_cpu_platform
    force_cpu_platform(n_devices)
    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet_init

    hvd.init()
    assert hvd.size() == n_devices
    params = resnet_init(jax.random.PRNGKey(0), 18, num_classes=100)
    base = optax.sgd(0.01, momentum=0.9)
    o1 = hvd.DistributedOptimizer(base, backward_passes_per_step=2,
                                  early_reduction=True, zero_stage=1)
    o2 = hvd.DistributedOptimizer(base, backward_passes_per_step=2,
                                  zero_stage=2)
    s1 = o1.init(params)
    s2 = o2.init(params)
    g1 = hvd.grad_accum_bytes(s1)
    g2 = hvd.grad_accum_bytes(s2)
    pl = hvd.zero3_placement(params)
    emit({
        "n": n_devices,
        "grad_accum_bytes_stage1": g1,
        "grad_accum_bytes_stage2": g2,
        "grad_accum_reduction": round(g1 / max(1, g2), 4),
        "param_bytes_replicated": pl.full_bytes,
        "param_bytes_resident_stage3": pl.resident_bytes(),
        "param_resident_reduction": round(
            pl.full_bytes / max(1, pl.resident_bytes()), 4),
        "opt_state_bytes_stage1": hvd.optimizer_state_bytes(s1),
    })


def zero_memory_report(timeout: float = 600.0) -> dict:
    """ZeRO ladder memory pipeline: the gradient-accumulator claim at
    n=2 (stage 2 halves it exactly with backward_passes_per_step >= 2)
    and the parameter-residency claim at n=8 (stage 3 keeps ~1/N
    resident outside the live bucket window), each measured in a child
    process on its own virtual mesh."""
    out = {}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for n in (2, 8):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--zero-bytes-child", str(n)],
            capture_output=True, text=True, timeout=timeout, env=env)
        if r.returncode != 0:
            log(f"zero-bytes child n={n} rc={r.returncode} "
                f"stderr tail: {r.stderr[-1000:]}")
            continue
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        out[f"n{n}"] = rec
        log(f"zero bytes n={n}: grad accum "
            f"{rec['grad_accum_bytes_stage1']} -> "
            f"{rec['grad_accum_bytes_stage2']} "
            f"({rec['grad_accum_reduction']}x, stage 2); params "
            f"{rec['param_bytes_replicated']} -> "
            f"{rec['param_bytes_resident_stage3']} resident "
            f"({rec['param_resident_reduction']}x, stage 3)")
    return out


def run_reshard_child() -> None:
    """Child mode: live-reshard vs checkpoint-restore timing at n=2
    (docs/RESHARD.md).  Two simulated old ranks hold ~4 MB of ZeRO
    shard rows; the live path publishes + fetches through the in-memory
    transport under the default peak ceiling, the legacy path does a
    durable checkpoint save + restore + local restack.  Prints one JSON
    line with both wall times and the measured staging peak."""
    import tempfile

    import numpy as np

    from horovod_tpu.parallel import reshard as rs
    from horovod_tpu.utils.checkpoint import CheckpointManager

    rng = np.random.RandomState(0)
    ge = (1 << 19, 1 << 19)  # two 512k-elem f32 groups = 4 MB total
    n_old = 2
    rows = tuple(rng.randn(n_old, -(-e // n_old)).astype(np.float32)
                 for e in ge)
    peak = rs.default_peak_bytes()

    t = rs.LocalTransport()
    t0 = time.perf_counter()
    for r in range(n_old):
        specs, data = rs.param_streams(rows, ge, n_old, r)
        rs.reshard_streams(specs, data, n_old, 1, r, None, t,
                           tag="bench", peak_bytes=peak)
    specs, _ = rs.param_streams(rows, ge, n_old, 0)
    streams, rep = rs.reshard_streams(
        specs, None, n_old, 1, None, 0, t, tag="bench", peak_bytes=peak)
    live_rows = rs.streams_to_param_rows(
        streams, ge, tuple(r.dtype for r in rows), 1, 0)
    live_ms = (time.perf_counter() - t0) * 1000.0

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        t0 = time.perf_counter()
        mgr.save(0, {"rows": list(rows)}, force=True)
        restored = mgr.restore(0)
        ck_rows = tuple(rs.reshard_shard_rows(np.asarray(r), e, 1)
                        for r, e in zip(restored["rows"], ge))
        restore_ms = (time.perf_counter() - t0) * 1000.0

    bitwise = all(a.tobytes() == b.tobytes()
                  for a, b in zip(live_rows, ck_rows))
    emit({
        "n_old": n_old, "n_new": 1,
        "state_bytes": int(sum(r.nbytes for r in rows)),
        "live_ms": round(live_ms, 2),
        "restore_ms": round(restore_ms, 2),
        "speedup": round(restore_ms / max(live_ms, 1e-6), 2),
        "peak_bytes": rep.peak_bytes,
        "peak_ceiling": peak,
        "chunks": rep.chunks,
        "bitwise_vs_restore": bitwise,
    })


def reshard_report(timeout: float = 600.0) -> dict:
    """Live-reshard extra: redistribute-vs-restore wall time and the
    measured staging peak at n=2, in a child process
    (docs/RESHARD.md)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--reshard-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        log(f"reshard child rc={r.returncode} "
            f"stderr tail: {r.stderr[-1000:]}")
        return {}
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    log(f"reshard n=2->1: live {rec['live_ms']} ms vs "
        f"save+restore+restack {rec['restore_ms']} ms "
        f"({rec['speedup']}x), peak {rec['peak_bytes']} / "
        f"{rec['peak_ceiling']} bytes, bitwise="
        f"{rec['bitwise_vs_restore']}")
    return rec


def run_chaos_child() -> None:
    """Runner-launched rank of the chaos bench: one fault-loaded
    `ChaosSoak` (horovod_tpu/faults/chaos.py, docs/CHAOS.md) per rank,
    result JSON written to $HVD_CHAOS_OUT/rank{r}.json."""
    import horovod_tpu as hvd
    from horovod_tpu.faults.chaos import ChaosSoak

    hvd.init()
    res = ChaosSoak(
        seed=int(os.environ.get("HVD_CHAOS_SEED", "7"))).run()
    with open(os.path.join(os.environ["HVD_CHAOS_OUT"],
                           f"rank{hvd.rank()}.json"), "w") as f:
        json.dump(res, f)
    hvd.shutdown()


def _pctl(xs, q):
    """Nearest-rank percentile of a sorted list."""
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def chaos_report(timeout: float = 600.0) -> dict:
    """Chaos extra: MTTR percentiles + steps-lost-per-injection from a
    real np>=2 fault-loaded soak (HOROVOD_BENCH_CHAOS_NP, default 2)."""
    np_ = int(os.environ.get("HOROVOD_BENCH_CHAOS_NP", "2"))
    out = tempfile.mkdtemp(prefix="bench_chaos_")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HVD_CHAOS_OUT"] = out
    env.setdefault("HOROVOD_CHAOS_GENERATIONS", "6")
    env.setdefault("HOROVOD_CHAOS_STEPS_PER_GEN", "5")
    env.setdefault("HOROVOD_AUTOTUNE", "1")
    env.setdefault("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "1")
    env.setdefault("HOROVOD_TIMELINE", os.path.join(out, "tl.json"))
    env.setdefault("HOROVOD_TIMELINE_ALL_RANKS", "1")
    env.setdefault("HOROVOD_TIMELINE_MARK_CYCLES", "1")
    env.setdefault("HOROVOD_TIMELINE_DISABLE_NATIVE", "1")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         sys.executable, os.path.abspath(__file__), "--chaos-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        log(f"chaos fleet rc={r.returncode} "
            f"stderr tail: {r.stderr[-1500:]}")
        return {}
    with open(os.path.join(out, "rank0.json")) as f:
        res = json.load(f)
    events = res["events"]
    mttr = sorted(float(e["mttr_ms"]) for e in events
                  if e["outcome"] == "recovered")
    lost = [int(e["steps_lost"]) for e in events]
    bests = [w["autotune_best"] for w in res["windows"]
             if w.get("autotune_best") is not None]
    return {
        "np": np_,
        "generations": len(res["windows"]),
        "events": len(events),
        "kinds": sorted(res["kinds_injected"]),
        "recovered": sum(1 for e in events
                         if e["outcome"] == "recovered"),
        "degraded": sum(1 for e in events if e["outcome"] == "degraded"),
        "mttr_p50_ms": round(_pctl(mttr, 0.50), 2) if mttr else None,
        "mttr_p99_ms": round(_pctl(mttr, 0.99), 2) if mttr else None,
        "steps_lost_total": sum(lost),
        "steps_lost_per_injection": (round(sum(lost) / len(lost), 3)
                                     if lost else 0.0),
        "loud_reinits": res["loud_reinits"],
        "reactions": res["reactions"],
        "autotune_best_final": bests[-1] if bests else None,
        "split_brain": res["split_brain"],
        "final_digest_mismatch": res["final_digest_mismatch"],
    }


def main_chaos():
    """`bench.py --chaos`: run the chaos extra standalone and append the
    record to BENCH_chaos.json (JSON lines, same provenance stamps and
    HOROVOD_BENCH_CACHE_MAX_AGE_H stale gate as BENCH_serve.json —
    duplicated here because the bench parent never imports the
    package)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo, "BENCH_chaos.json")
    prev = None
    if os.path.exists(path):
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if lines:
            prev = json.loads(lines[-1])
            age_h = (time.time()
                     - prev.get("captured_unix", 0.0)) / 3600.0
            prev["stale"] = age_h > CACHE_MAX_AGE_H
            if prev["stale"]:
                log(f"previous chaos record is {age_h:.1f}h old "
                    f"(> {CACHE_MAX_AGE_H:g}h gate) — not comparing")
    try:
        rec = chaos_report()
    except Exception as e:  # noqa: BLE001
        log(f"chaos bench failed: {type(e).__name__}: {e}")
        rec = {}
    if not rec:
        emit({"bench": "chaos", "error": "chaos soak failed; see stderr"})
        sys.exit(1)
    rec = {"bench": "chaos", **rec}
    if (prev is not None and not prev.get("stale")
            and prev.get("bench") == "chaos"
            and prev.get("mttr_p50_ms") and rec.get("mttr_p50_ms")):
        rec["mttr_p50_vs_prev"] = round(
            rec["mttr_p50_ms"] / prev["mttr_p50_ms"], 3)
    now = time.time()
    rec["captured_unix"] = now
    rec["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime(now))
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")
    log(f"chaos np={rec['np']}: {rec['events']} events "
        f"({rec['recovered']} recovered / {rec['degraded']} degraded), "
        f"MTTR p50/p99 {rec['mttr_p50_ms']}/{rec['mttr_p99_ms']} ms, "
        f"{rec['steps_lost_per_injection']} steps lost/injection, "
        f"{len(rec['kinds'])} fault kinds")
    emit(rec)


def run_autoscale_child() -> None:
    """`bench.py --autoscale-child`: the autoscaler A/B + scale-event
    chaos (horovod_tpu/serve/autoscale.py, docs/AUTOSCALE.md), result
    JSON written to $HVD_AUTOSCALE_OUT.

    For each traffic shape the same seeded trace drives the REAL
    decision core twice — autoscaled vs a static fleet pinned at the
    autoscaled run's MEAN size (same chips, only the control loop
    differs) — and records SLO-violation-minutes and chip-hours.  The
    bursty shape is the acceptance anchor: autoscaling must win on
    violation-minutes at the same mean size.  Then run_scale_chaos
    fires serve.replica_die DURING live grow events on a real replica
    fleet and must report every event recovered digest-verified."""
    from horovod_tpu.serve.autoscale import (
        AutoscaleConfig,
        run_scale_chaos,
        simulate_autoscale,
    )
    from horovod_tpu.serve.loadgen import make_shaped_trace

    cfg = AutoscaleConfig(min_replicas=1, max_replicas=8,
                          cooldown_steps=4, dwell_steps=2, grow_step=2)
    shapes = {
        "burst": dict(base_every=4.0, burst_every=128, burst_size=80),
        "diurnal": dict(base_every=4.0, period=256, amplitude=0.9),
        "multi_tenant": dict(base_every=4.0),
    }
    ab = {}
    for shape, kw in shapes.items():
        trace = make_shaped_trace(shape, 7, 500, 64, **kw)
        auto = simulate_autoscale(trace, cfg)
        static = simulate_autoscale(
            trace, cfg, static_size=max(1, round(auto["fleet_mean"])))
        ab[shape] = {"autoscaled": auto, "static": static,
                     "violation_minutes_saved": round(
                         static["slo_violation_minutes"]
                         - auto["slo_violation_minutes"], 4)}

    chaos = run_scale_chaos(
        n_events=int(os.environ.get("HVD_AUTOSCALE_EVENTS", "2")),
        seed=0)
    with open(os.environ["HVD_AUTOSCALE_OUT"], "w") as f:
        json.dump({"ab": ab, "scale_chaos": chaos}, f)


def autoscale_report(timeout: float = 600.0) -> dict:
    """Autoscale extra: run the child out-of-process (the parent never
    imports the package) and flatten its record."""
    out = tempfile.mkdtemp(prefix="bench_autoscale_")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HVD_AUTOSCALE_OUT"] = os.path.join(out, "autoscale.json")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--autoscale-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        log(f"autoscale child rc={r.returncode} "
            f"stderr tail: {r.stderr[-1500:]}")
        return {}
    with open(env["HVD_AUTOSCALE_OUT"]) as f:
        res = json.load(f)
    burst = res["ab"]["burst"]
    chaos = res["scale_chaos"]
    return {
        "ab": res["ab"],
        "burst_auto_violation_minutes":
            burst["autoscaled"]["slo_violation_minutes"],
        "burst_static_violation_minutes":
            burst["static"]["slo_violation_minutes"],
        "burst_fleet_mean": burst["autoscaled"]["fleet_mean"],
        "burst_chip_hours": burst["autoscaled"]["chip_hours"],
        "autoscaled_wins_burst":
            burst["autoscaled"]["slo_violation_minutes"]
            < burst["static"]["slo_violation_minutes"],
        "scale_chaos": chaos,
        "scale_events": len(chaos.get("events", [])),
        "scale_events_faulted": sum(
            1 for e in chaos.get("events", []) if e["faulted"]),
        "all_recovered": chaos.get("all_recovered", False),
    }


def main_autoscale():
    """`bench.py --autoscale`: run the autoscale extra standalone and
    append the record to BENCH_autoscale.json (JSON lines, same
    provenance stamps and HOROVOD_BENCH_CACHE_MAX_AGE_H stale gate as
    the other bench files)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo, "BENCH_autoscale.json")
    prev = None
    if os.path.exists(path):
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if lines:
            prev = json.loads(lines[-1])
            age_h = (time.time()
                     - prev.get("captured_unix", 0.0)) / 3600.0
            prev["stale"] = age_h > CACHE_MAX_AGE_H
            if prev["stale"]:
                log(f"previous autoscale record is {age_h:.1f}h old "
                    f"(> {CACHE_MAX_AGE_H:g}h gate) — not comparing")
    try:
        rec = autoscale_report()
    except Exception as e:  # noqa: BLE001
        log(f"autoscale bench failed: {type(e).__name__}: {e}")
        rec = {}
    if not rec:
        emit({"bench": "autoscale",
              "error": "autoscale bench failed; see stderr"})
        sys.exit(1)
    rec = {"bench": "autoscale", **rec}
    if (prev is not None and not prev.get("stale")
            and prev.get("bench") == "autoscale"
            and prev.get("burst_auto_violation_minutes") is not None
            and rec.get("burst_auto_violation_minutes") is not None
            and prev["burst_auto_violation_minutes"] > 0):
        rec["burst_violation_vs_prev"] = round(
            rec["burst_auto_violation_minutes"]
            / prev["burst_auto_violation_minutes"], 3)
    now = time.time()
    rec["captured_unix"] = now
    rec["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime(now))
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")
    log(f"autoscale burst: auto {rec['burst_auto_violation_minutes']} "
        f"vs static {rec['burst_static_violation_minutes']} "
        f"violation-minutes at mean fleet {rec['burst_fleet_mean']} "
        f"(wins={rec['autoscaled_wins_burst']}); scale chaos "
        f"{rec['scale_events']} events "
        f"({rec['scale_events_faulted']} faulted), "
        f"all_recovered={rec['all_recovered']}")
    emit(rec)


def run_obs_child() -> None:
    """`bench.py --obs-child`: sampler-overhead A/B for the telemetry
    history plane (horovod_tpu/metrics/history.py, docs/TELEMETRY.md),
    emitted as one JSON line.

    Arm A runs an instrumented synthetic step loop (counter incs, gauge
    sets, histogram observes — the per-step shape of the real training
    instrumentation) with no sampler; arm B runs the identical loop with
    the background history sampler armed at an aggressive 20 Hz (the
    default cadence is 1 Hz, so this bounds the real overhead from
    above).  Arms are interleaved across repeats and medians compared,
    plus a direct per-sample() micro-measure over the full catalog."""
    import random

    from horovod_tpu.metrics import catalog, history

    rng = random.Random(7)

    def step():
        catalog.steps.inc()
        catalog.critical_path_ms.set(10.0 + rng.random())
        catalog.serve_e2e_latency.observe(0.01 + rng.random() * 0.002)
        catalog.serve_queue_delay.observe(rng.random() * 1e-3)
        # Stand-in compute so the loop is not 100% metrics calls.
        s = 0.0
        for i in range(200):
            s += i * 1e-6
        return s

    n_steps = int(os.environ.get("HVD_OBS_STEPS", "3000"))
    repeats = int(os.environ.get("HVD_OBS_REPEATS", "3"))

    def run_arm(sampled: bool) -> float:
        if sampled:
            history.start_history(interval=0.05)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            step()
        dt = time.perf_counter() - t0
        if sampled:
            history.stop_history()
        return dt

    run_arm(False)  # warmup (interpreter caches, registry children)
    plain, sampled = [], []
    for _ in range(repeats):
        plain.append(run_arm(False))
        sampled.append(run_arm(True))
    plain.sort()
    sampled.sort()
    t_a, t_b = _pctl(plain, 0.5), _pctl(sampled, 0.5)
    overhead_pct = max(0.0, (t_b - t_a) / t_a * 100.0)

    h = history.MetricsHistory(depth=64)
    h.sample()  # prime histogram-delta state
    t0 = time.perf_counter()
    k = 50
    for _ in range(k):
        h.sample()
    per_sample_us = (time.perf_counter() - t0) / k * 1e6
    emit({
        "steps": n_steps,
        "repeats": repeats,
        "step_us": round(t_a / n_steps * 1e6, 2),
        "sampler_overhead_pct": round(overhead_pct, 3),
        "per_sample_us": round(per_sample_us, 1),
        "series_tracked": len(h.series()),
    })


def obs_report(timeout: float = 600.0) -> dict:
    """Observability extra: (a) history-sampler overhead as % of step
    time from the A/B child, (b) anomaly-detection recall from a real
    np=2 fault-loaded soak (the chaos harness doubles as the detector's
    recall fixture — injected faults are ground truth)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--obs-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        log(f"obs child rc={r.returncode} "
            f"stderr tail: {r.stderr[-1000:]}")
        return {}
    rec = json.loads(r.stdout.strip().splitlines()[-1])

    np_ = int(os.environ.get("HOROVOD_BENCH_CHAOS_NP", "2"))
    out = tempfile.mkdtemp(prefix="bench_obs_")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HVD_CHAOS_OUT"] = out
    # Same fast-soak shape the tier-1 chaos test uses: 4 straggler-armed
    # generations then a one-shot rotation, so recall has ground truth.
    env.setdefault("HOROVOD_CHAOS_GENERATIONS", "5")
    env.setdefault("HOROVOD_CHAOS_STEPS_PER_GEN", "4")
    env.setdefault("HOROVOD_STRAGGLER_PATIENCE", "2")
    env.setdefault("HOROVOD_STRAGGLER_COOLDOWN", "1")
    env.setdefault("HOROVOD_AUTOTUNE", "1")
    env.setdefault("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "1")
    env.setdefault("HOROVOD_TIMELINE", os.path.join(out, "tl.json"))
    env.setdefault("HOROVOD_TIMELINE_ALL_RANKS", "1")
    env.setdefault("HOROVOD_TIMELINE_MARK_CYCLES", "1")
    env.setdefault("HOROVOD_TIMELINE_DISABLE_NATIVE", "1")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         sys.executable, os.path.abspath(__file__), "--chaos-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        log(f"obs chaos fleet rc={r.returncode} "
            f"stderr tail: {r.stderr[-1500:]}")
        return {}
    with open(os.path.join(out, "rank0.json")) as f:
        anom = json.load(f).get("anomaly", {})
    rec.update({
        "np": np_,
        "detection_recall": anom.get("recall"),
        "detected_kinds": anom.get("detected_kinds", []),
        "injected_kinds": anom.get("injected_kinds", []),
        "false_positives": anom.get("false_positives"),
    })
    return rec


def main_obs():
    """`bench.py --obs`: run the observability extra standalone and
    append the record to BENCH_obs.json (JSON lines, same provenance
    stamps and HOROVOD_BENCH_CACHE_MAX_AGE_H stale gate as
    BENCH_chaos.json)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo, "BENCH_obs.json")
    prev = None
    if os.path.exists(path):
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if lines:
            prev = json.loads(lines[-1])
            age_h = (time.time()
                     - prev.get("captured_unix", 0.0)) / 3600.0
            prev["stale"] = age_h > CACHE_MAX_AGE_H
            if prev["stale"]:
                log(f"previous obs record is {age_h:.1f}h old "
                    f"(> {CACHE_MAX_AGE_H:g}h gate) — not comparing")
    try:
        rec = obs_report()
    except Exception as e:  # noqa: BLE001
        log(f"obs bench failed: {type(e).__name__}: {e}")
        rec = {}
    if not rec:
        emit({"bench": "obs", "error": "obs bench failed; see stderr"})
        sys.exit(1)
    rec = {"bench": "obs", **rec}
    rec["overhead_budget_pct"] = 2.0
    rec["overhead_ok"] = rec["sampler_overhead_pct"] <= 2.0
    if (prev is not None and not prev.get("stale")
            and prev.get("bench") == "obs"
            and prev.get("per_sample_us") and rec.get("per_sample_us")):
        rec["per_sample_vs_prev"] = round(
            rec["per_sample_us"] / prev["per_sample_us"], 3)
    now = time.time()
    rec["captured_unix"] = now
    rec["captured_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime(now))
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")
    log(f"obs: sampler overhead {rec['sampler_overhead_pct']}% of step "
        f"time (budget 2%, ok={rec['overhead_ok']}), "
        f"{rec['per_sample_us']}us/sample over "
        f"{rec['series_tracked']} series; detection recall "
        f"{rec['detection_recall']} ({len(rec['detected_kinds'])}/"
        f"{len(rec['injected_kinds'])} kinds, "
        f"{rec['false_positives']} false positives)")
    emit(rec)


def _load_trace_core():
    """The fleet tracer's analyzer (horovod_tpu/trace/core.py), loaded
    by file path so the bench parent never imports the package (and so
    never pulls jax in — the same rule hvdlint follows)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "horovod_tpu", "trace", "core.py")
    spec = importlib.util.spec_from_file_location("_hvd_trace_core", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Side channel: the full JSON record of the most recent sim child, so
# callers that go through the `_run_sim` timing seam (the function the
# stats tests monkeypatch) can still read non-timing fields like
# opt_state_bytes.  None when the last probe failed or was stubbed out.
_LAST_SIM_RECORD = None


def _run_sim_record(n: int, distributed: bool, timeout: float,
                    legacy: bool = False, sharded: bool = False,
                    quant: bool = False, guard: bool = False,
                    fused: bool = False, timeline: "str | None" = None):
    """Run one sim child; return its full JSON record (or None).
    `timeline` arms HOROVOD_TIMELINE in the child so it appends the
    trace-measured synced pass (see run_sim_child)."""
    global _LAST_SIM_RECORD
    _LAST_SIM_RECORD = None
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_SHARD_OPTIMIZER", None)
    env.pop("HOROVOD_WIRE_POLICY", None)
    env.pop("HOROVOD_GUARD", None)
    env.pop("HOROVOD_FUSED_COLLECTIVES", None)
    env.pop("HOROVOD_TIMELINE", None)
    env.pop("HOROVOD_TIMELINE_MARK_CYCLES", None)
    if timeline:
        env["HOROVOD_TIMELINE"] = timeline
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if legacy:
        env["HOROVOD_BENCH_LEGACY_PIPELINE"] = "1"
    if sharded:
        env["HOROVOD_SHARD_OPTIMIZER"] = "1"
    if quant:
        env["HOROVOD_WIRE_POLICY"] = "auto"
    if guard:
        env["HOROVOD_GUARD"] = "1"
    if fused:
        env["HOROVOD_FUSED_COLLECTIVES"] = "1"
    cmd = [sys.executable, os.path.abspath(__file__), "--sim-child", str(n)]
    if not distributed:
        cmd.append("--no-dist")
    try:
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        log(f"sim-scaling child n={n} timed out")
        return None
    if r.returncode != 0:
        log(f"sim-scaling child n={n} rc={r.returncode} "
            f"stderr tail: {r.stderr[-500:]}")
        return None
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    _LAST_SIM_RECORD = rec
    return rec


def _run_sim(n: int, distributed: bool, timeout: float,
             legacy: bool = False, sharded: bool = False,
             quant: bool = False, guard: bool = False,
             fused: bool = False):
    rec = _run_sim_record(n, distributed, timeout, legacy=legacy,
                          sharded=sharded, quant=quant, guard=guard,
                          fused=fused)
    return None if rec is None else rec["step_time_s"]


def sim_scaling_efficiency(timeout: float = 600.0,
                           runs: "int | None" = None):
    """Simulated scaling efficiency on the virtual CPU mesh —
    gate-quality estimator.

    The n virtual devices share the host's physical cores, so the ideal
    n=8 step (global batch 8x) takes 8x the n=1 step's wall time; any
    extra time is collective/framework overhead.  Efficiency is therefore
    8*T1/T8 — the shared-core analog of per-chip throughput retention on
    real hardware.

    Estimator (tightened per the r04 verdict's gate requirement): the
    per-chip batch is pinned at 16 (see run_sim_child) and `runs` >= 7
    PAIRED (t1, t8) samples are collected — pairing adjacent-in-time
    runs cancels slow host-load drift.  A pair with eff > 1.0 is
    physically impossible on the shared-core mesh (contention inflated
    its t1) and is REJECTED as invalid rather than kept or clamped —
    clamping would bias the center up exactly when the host is loaded,
    keeping it would blow the spread with a value known to be noise.
    The reported center is the TRIMMED median (drop the min and max
    pair, median of the rest), spread is the central-3 order-statistic
    spread, and a bootstrap percentile CI (2.5/97.5, deterministic
    seed) of the trimmed median ships alongside so the >=0.90 gate can
    be read against an interval, not a point.  Returns
    (median, spread, effs, ci, n_rejected, extras) where `extras` is a
    dict with the collective-share decomposition.

    Collective share is T8(dist) - T8(no dist) — the same
    decomposition the reference's timeline gives per tensor — measured
    for BOTH pipelines: the overlap-aware default (reverse-availability
    buckets + fused per-bucket apply) and the legacy barriered path
    (HOROVOD_BENCH_LEGACY_PIPELINE), so the record carries a
    before/after comparison of how much per-step time the collectives
    cost under each.
    """
    global _LAST_SIM_RECORD
    import numpy as _np

    if runs is None:
        runs = int(os.environ.get("HOROVOD_BENCH_SIM_RUNS", "7"))
    max_runs = max(runs,
                   int(os.environ.get("HOROVOD_BENCH_SIM_MAX_RUNS", "9")))
    effs, t1s, t8s = [], [], []
    opt_bytes_repl = None
    rejected = 0
    attempts, max_attempts = 0, 2 * max_runs + 4
    while len(effs) < runs and attempts < max_attempts:
        attempts += 1
        t1 = _run_sim(1, True, timeout)
        if t1 is None:
            # Don't pay the (much longer) n=8 child for a pair that is
            # already dead; retry, bounded by max_attempts so a broken
            # mesh can't loop.
            log(f"sim-scaling attempt {attempts}: n=1 child failed, "
                f"retrying")
            continue
        _LAST_SIM_RECORD = None
        t8 = _run_sim(8, True, timeout)
        if t8 is None:
            log(f"sim-scaling attempt {attempts}: n=8 child failed, "
                f"retrying")
            continue
        if _LAST_SIM_RECORD is not None:
            opt_bytes_repl = _LAST_SIM_RECORD.get("opt_state_bytes",
                                                  opt_bytes_repl)
        eff = 8.0 * t1 / t8
        if eff > 1.0:
            # Superlinear scaling cannot happen on a shared-core mesh:
            # the pair's t1 was inflated by host contention.  Invalid
            # measurement, not an unusually good one — reject it (r04
            # verdict: "discard eff > 1.0 pairs as invalid").
            rejected += 1
            log(f"sim-scaling attempt {attempts}: eff {eff:.4f} > 1.0 "
                f"(contention-inflated t1) — pair rejected")
            continue
        log(f"sim-scaling pair {len(effs)}: n1={t1*1e3:.1f} ms "
            f"n8={t8*1e3:.1f} ms -> eff {eff:.4f}")
        effs.append(eff)
        t1s.append(t1)
        t8s.append(t8)
        # Adaptive widening: transient host contention shows up as a
        # blown spread; extra pairs let the trimmed median reject more
        # outliers (gate asks spread < 0.03 — r04 verdict task 4).
        if (len(effs) == runs and runs < max_runs
                and max(effs) - min(effs) > 0.03):
            log(f"sim-scaling: spread {max(effs) - min(effs):.4f} > 0.03 "
                f"after {runs} pairs; widening to {max_runs}")
            runs = max_runs
    if len(effs) < 3:
        log(f"sim-scaling: only {len(effs)} valid pairs "
            f"({rejected} rejected) — no estimate")
        return None
    extras = {}
    t8_nodist = _run_sim(8, False, timeout)
    if t8_nodist is not None and t8s:
        t8m = sorted(t8s)[len(t8s) // 2]
        share = (t8m - t8_nodist) / t8m
        log(f"sim-scaling n=8 compute-only: {t8_nodist*1e3:.1f} ms/step "
            f"-> collective share {(t8m - t8_nodist)*1e3:.1f} ms/step "
            f"({100 * share:.1f}%)")
        extras["t8_ms"] = round(t8m * 1e3, 1)
        extras["t8_nodist_ms"] = round(t8_nodist * 1e3, 1)
        extras["collective_share"] = round(share, 4)
        # Before/after: the legacy barriered pipeline's n=8 step on the
        # same mesh, timed back-to-back so host load is comparable.
        t8_legacy = _run_sim(8, True, timeout, legacy=True)
        if t8_legacy is not None:
            legacy_share = (t8_legacy - t8_nodist) / t8_legacy
            log(f"sim-scaling n=8 legacy pipeline: {t8_legacy*1e3:.1f} "
                f"ms/step -> collective share "
                f"{(t8_legacy - t8_nodist)*1e3:.1f} ms/step "
                f"({100 * legacy_share:.1f}%)")
            extras["t8_legacy_ms"] = round(t8_legacy * 1e3, 1)
            extras["collective_share_legacy"] = round(legacy_share, 4)
        # ZeRO-1 pipeline: n=8 step with sharded optimizer state
        # (reduce-scatter + local shard update + param allgather), plus
        # the replicated-vs-sharded per-chip state-bytes comparison the
        # memory claim rests on (docs/SHARDED_OPTIMIZER.md).
        _LAST_SIM_RECORD = None
        t8_sharded = _run_sim(8, True, timeout, sharded=True)
        rec_sharded = _LAST_SIM_RECORD
        if t8_sharded is not None:
            sharded_share = (t8_sharded - t8_nodist) / t8_sharded
            log(f"sim-scaling n=8 sharded pipeline: {t8_sharded*1e3:.1f} "
                f"ms/step -> collective share "
                f"{(t8_sharded - t8_nodist)*1e3:.1f} ms/step "
                f"({100 * sharded_share:.1f}%)")
            extras["t8_sharded_ms"] = round(t8_sharded * 1e3, 1)
            extras["collective_share_sharded"] = round(sharded_share, 4)
            sb = (rec_sharded.get("opt_state_bytes")
                  if rec_sharded is not None else None)
            rb = opt_bytes_repl
            if sb and rb:
                log(f"sim-scaling opt-state bytes/chip: replicated {rb} "
                    f"-> sharded {sb} ({rb / sb:.1f}x smaller)")
                extras["opt_state_bytes_replicated"] = int(rb)
                extras["opt_state_bytes_sharded"] = int(sb)
        # Quantized-wire pipeline: n=8 step with HOROVOD_WIRE_POLICY=auto
        # (big gradient buckets ride the int8 ring, small stay exact —
        # docs/WIRE.md), plus the static wire-byte savings of the policy.
        t8_quant = _run_sim(8, True, timeout, quant=True)
        rec_quant = _LAST_SIM_RECORD
        if t8_quant is not None:
            quant_share = (t8_quant - t8_nodist) / t8_quant
            log(f"sim-scaling n=8 quant pipeline: {t8_quant*1e3:.1f} "
                f"ms/step -> collective share "
                f"{(t8_quant - t8_nodist)*1e3:.1f} ms/step "
                f"({100 * quant_share:.1f}%)")
            extras["t8_quant_ms"] = round(t8_quant * 1e3, 1)
            extras["collective_share_quant"] = round(quant_share, 4)
            saved = (rec_quant.get("wire_bytes_saved")
                     if rec_quant is not None else None)
            raw = (rec_quant.get("wire_bytes_raw")
                   if rec_quant is not None else None)
            if saved and raw:
                log(f"sim-scaling wire bytes/step: raw {raw} -> saved "
                    f"{saved} ({raw / (raw - saved):.1f}x less on the "
                    "wire)")
                extras["wire_bytes_saved"] = int(saved)
                extras["wire_bytes_raw"] = int(raw)
        # Training-health guardian: the same overlap pipeline with the
        # fused non-finite sentinel + skip-step gate armed
        # (HOROVOD_GUARD=1, docs/GUARD.md).  The delta vs the plain
        # overlap median is the no-fault guard overhead — the GUARD.md
        # claim is that it stays within ~1% of the step.
        t8_guard = _run_sim(8, True, timeout, guard=True)
        if t8_guard is not None:
            overhead = (t8_guard - t8m) / t8m
            log(f"sim-scaling n=8 guard pipeline: {t8_guard*1e3:.1f} "
                f"ms/step -> sentinel overhead "
                f"{(t8_guard - t8m)*1e3:+.1f} ms/step "
                f"({100 * overhead:+.1f}%)")
            extras["t8_guard_ms"] = round(t8_guard * 1e3, 1)
            extras["guard_overhead"] = round(overhead, 4)
        # Fused computation-collective pipeline: the overlap path with
        # HOROVOD_FUSED_COLLECTIVES=1 (docs/FUSED_COLLECTIVES.md) —
        # bucket reductions software-pipelined in fused_chunk_bytes
        # chunks.  collective_share_fused vs collective_share is the
        # intra-bucket wire time the chunking hides; the per-chunk
        # occupancy stats ship from the child's static schedule.
        _LAST_SIM_RECORD = None
        t8_fused = _run_sim(8, True, timeout, fused=True)
        rec_fused = _LAST_SIM_RECORD
        if t8_fused is not None:
            fused_share = (t8_fused - t8_nodist) / t8_fused
            log(f"sim-scaling n=8 fused pipeline: {t8_fused*1e3:.1f} "
                f"ms/step -> collective share "
                f"{(t8_fused - t8_nodist)*1e3:.1f} ms/step "
                f"({100 * fused_share:.1f}%)")
            extras["t8_fused_ms"] = round(t8_fused * 1e3, 1)
            extras["collective_share_fused"] = round(fused_share, 4)
            if rec_fused is not None:
                for key in ("fused_buckets", "fused_chunks_total",
                            "fused_chunk_bytes", "fused_occupancy_mean",
                            "fused_occupancy_max"):
                    if key in rec_fused:
                        extras[key] = rec_fused[key]
                if "fused_occupancy_mean" in rec_fused:
                    log(f"sim-scaling fused pipeline occupancy: mean "
                        f"{rec_fused['fused_occupancy_mean']:.3f} max "
                        f"{rec_fused['fused_occupancy_max']:.3f} over "
                        f"{rec_fused.get('fused_chunks_total', 0)} "
                        f"chunks in {rec_fused.get('fused_buckets', 0)} "
                        f"buckets")

        # Trace-MEASURED attribution (docs/TRACE.md): re-run the n=8
        # dist/no-dist pair with the timeline armed; the fleet tracer's
        # analyzer reads the per-step critical path from device-synced
        # CYCLE windows instead of wall-clock subtraction.  The sim mesh
        # is one process, so the cross-rank skew component is
        # structurally zero here — skew_share becomes meaningful on
        # multi-process (np>=2) timelines.  Gated on a real child record
        # from the probes above: a stubbed/recordless run has no sim
        # children to re-launch.
        if _LAST_SIM_RECORD is not None or rec_fused is not None:
            try:
                tdir = tempfile.mkdtemp(prefix="hvd_bench_trace_")
                dist_tl = os.path.join(tdir, "dist.json")
                nodist_tl = os.path.join(tdir, "nodist.json")
                _run_sim_record(8, True, timeout, timeline=dist_tl)
                _run_sim_record(8, False, timeout, timeline=nodist_tl)
                tc = _load_trace_core()
                cp_d = tc.analyze([dist_tl])["summary"]
                cp_n = tc.analyze([nodist_tl])["summary"]
                d, nd = (cp_d["critical_path_ms_median"],
                         cp_n["critical_path_ms_median"])
                if d > 0 and nd > 0:
                    extras["critical_path_ms_measured"] = round(d, 1)
                    extras["collective_share_measured"] = round(
                        max(0.0, 1.0 - nd / d), 4)
                    extras["skew_share"] = cp_d["skew_share"]
                    log(f"sim-scaling trace-measured: critical path "
                        f"{d:.1f} ms/step, collective share "
                        f"{100 * extras['collective_share_measured']:.1f}"
                        f"% (measured), skew share "
                        f"{100 * extras['skew_share']:.1f}%")
            except Exception as e:  # noqa: BLE001 — must not sink bench
                log(f"sim-scaling trace-measured attribution "
                    f"skipped: {e}")

    def _trimmed_median(vals):
        s = _np.sort(_np.asarray(vals))
        if len(s) >= 5:
            s = s[1:-1]                       # drop min and max pair
        return float(_np.median(s))

    median = _trimmed_median(effs)
    s = sorted(effs)
    if len(s) >= 5:
        # Spread over the central 3 order statistics — the agreement of
        # the values the trimmed median rests on (the raw per-run list
        # still ships in the JSON for transparency).
        mid = (len(s) - 3) // 2
        spread = s[mid + 2] - s[mid]
    else:
        spread = max(effs) - min(effs)
    # Bootstrap percentile CI of the trimmed median.  Deterministic
    # seed: the interval must be a function of the data, not the run.
    rng = _np.random.default_rng(0)
    arr = _np.asarray(effs)
    boots = [_trimmed_median(rng.choice(arr, size=len(arr)))
             for _ in range(2000)]
    ci = (float(_np.percentile(boots, 2.5)),
          float(_np.percentile(boots, 97.5)))
    log(f"sim-scaling: trimmed median {median:.4f}, spread "
        f"{spread:.4f}, CI [{ci[0]:.4f}, {ci[1]:.4f}] over "
        f"{len(effs)} valid pairs ({rejected} rejected)")
    return median, spread, effs, ci, rejected, extras


# ---------------------------------------------------------------------------
# Transformer tok/s (flagship model, single chip)
# ---------------------------------------------------------------------------

def run_transformer_bench(d_model=512, seq=1024, batch=8, layers=8) -> float:
    """tok/s of one fwd+bwd+update step of the flagship transformer
    (dense config) on the current device — the long-context flagship's
    single-chip number next to the ResNet headline."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import (
        TransformerConfig, transformer_init, transformer_ref_loss,
    )

    cfg = TransformerConfig(
        vocab_size=8192, d_model=d_model, n_heads=d_model // 64,
        d_head=64, d_ff=4 * d_model, n_layers=layers)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    x, y = tokens[:, :-1], tokens[:, 1:]

    def step(carry, batch_xy):
        params, opt_state = carry
        xb, yb = batch_xy

        def loss_fn(p):
            return transformer_ref_loss(p, xb, yb, cfg)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    # Megastep (utils/megastep.py): k steps per dispatch amortizes the
    # fixed host->device dispatch latency.  k=8 by default;
    # HOROVOD_BENCH_MEGASTEP=1 restores one-dispatch-per-step timing.
    from horovod_tpu.utils.megastep import repeat_steps

    k = int(os.environ.get("HOROVOD_BENCH_MEGASTEP", "8"))
    fused = repeat_steps(step, k)
    carry = (params, opt_state)

    warmup, iters = 2, 4
    for _ in range(warmup):
        carry, loss = fused(carry, (x, y))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = fused(carry, (x, y))
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / (iters * k)
    return batch * seq / dt


# ---------------------------------------------------------------------------
# Keras-path measurement (BASELINE config 3: TF2 Keras DistributedOptimizer)
# ---------------------------------------------------------------------------

def _keras_model_and_data():
    import numpy as np
    import tensorflow as tf

    tf.random.set_seed(0)
    batch = 64
    x = np.random.randn(batch, 28, 28, 1).astype("float32")
    y = np.random.randint(0, 10, (batch,))
    model = tf.keras.Sequential([
        tf.keras.layers.Conv2D(16, 3, activation="relu",
                               input_shape=(28, 28, 1)),
        tf.keras.layers.MaxPooling2D(),
        tf.keras.layers.Conv2D(32, 3, activation="relu"),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(10),
    ])
    return model, x, y, batch


def _time_keras(model, x, y, batch, warmup=2, iters=8) -> float:
    for _ in range(warmup):
        model.train_on_batch(x, y)
    t0 = time.perf_counter()
    for _ in range(iters):
        model.train_on_batch(x, y)
    return batch * iters / (time.perf_counter() - t0)


def run_keras_bench():
    """(distributed_img_sec, plain_img_sec) of the Keras frontend path:
    a small convnet trained through
    hvd.tensorflow.keras.DistributedOptimizer, next to the IDENTICAL
    model/compile WITHOUT horovod on the same host — the denominator
    that makes the bridge overhead falsifiable (r03 verdict task 5;
    reference: pytorch_synthetic_benchmark.py's per-rank + total img/s
    reporting discipline)."""
    import tensorflow as tf

    import horovod_tpu.tensorflow.keras as hvd_k

    loss_fn = tf.keras.losses.SparseCategoricalCrossentropy(from_logits=True)

    model, x, y, batch = _keras_model_and_data()
    model.compile(optimizer=tf.keras.optimizers.SGD(0.01), loss=loss_fn)
    plain = _time_keras(model, x, y, batch)

    model, x, y, batch = _keras_model_and_data()
    opt = hvd_k.DistributedOptimizer(tf.keras.optimizers.SGD(0.01))
    model.compile(optimizer=opt, loss=loss_fn)
    dist = _time_keras(model, x, y, batch)
    return dist, plain


# ---------------------------------------------------------------------------
# Main bench
# ---------------------------------------------------------------------------

def run_bench() -> dict:
    # Experiment hook: extra XLA flags (e.g. latency-hiding scheduler
    # sweeps) without editing the harness.
    extra_flags = os.environ.get("HOROVOD_BENCH_XLA_FLAGS")
    if extra_flags:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + extra_flags).strip()
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet_init

    hvd.init()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; jax found platform="
            f"{dev.platform!r}.  A CPU run yields no device metric "
            f"(the chip-independent reports are `--cpu-reports`).")
    # Reference benchmark: 224x224 synthetic images (docs/benchmarks.rst /
    # pytorch_synthetic_benchmark.py).  The reference's batch 64 is a
    # GPU-era choice; the v5e MXU wants larger batches, so the default
    # is 256 per chip.  HOROVOD_BENCH_BATCH overrides.
    batch = int(os.environ.get("HOROVOD_BENCH_BATCH", 0)) or 256
    image = 224
    warmup, iters = 5, 20
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={len(jax.devices())} batch={batch} image={image}")

    rng = jax.random.PRNGKey(42)
    v = resnet_init(rng, 50, num_classes=1000)
    cfg = v["config"]
    opt = optax.sgd(0.0125, momentum=0.9)

    x = jax.random.normal(jax.random.PRNGKey(0), (batch, image, image, 3),
                          jnp.bfloat16).astype(jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)

    def fresh_state():
        vv = resnet_init(rng, 50, num_classes=1000)
        st = {"params": vv["params"], "batch_stats": vv["batch_stats"]}
        return st, opt.init(st["params"])

    # --- framework path: one SPMD program over the mesh ---
    state, opt_state = fresh_state()
    fw_step = hvd.data_parallel(build_step(opt, cfg, distributed=True))
    sb = hvd.shard_batch((x, y))
    t_fw, _, _ = time_steps(fw_step, state, opt_state, sb, warmup, iters)
    fw_imgsec = batch / t_fw / hvd.size()  # per chip
    log(f"framework: {t_fw*1e3:.1f} ms/step, {fw_imgsec:.1f} img/s/chip")

    # --- raw-JAX baseline: same work, plain jit, no framework ---
    state, opt_state = fresh_state()
    raw_step = jax.jit(build_step(opt, cfg, distributed=False),
                       donate_argnums=(0, 1))
    t_raw, _, _ = time_steps(raw_step, state, opt_state, (x, y),
                             warmup, iters)
    raw_imgsec = batch / t_raw
    log(f"raw jax:   {t_raw*1e3:.1f} ms/step, {raw_imgsec:.1f} img/s/chip")

    # --- Keras frontend path (BASELINE config 3) ---
    keras_img_sec, keras_plain = run_keras_bench()
    log(f"keras_img_sec: {keras_img_sec:.1f} img/s through "
        f"DistributedOptimizer vs plain-Keras {keras_plain:.1f} img/s "
        f"-> keras_vs_baseline {keras_img_sec / keras_plain:.4f}")

    # --- transformer tok/s (toy d512 config, 1 chip) ---
    tfm_tok_s = run_transformer_bench()
    log(f"transformer_tok_s: {tfm_tok_s:.0f} tok/s "
        f"(1-chip fwd+bwd, d512 T1024 bf16)")

    return {
        "metric": "resnet50_synthetic_img_sec_per_chip",
        "value": round(fw_imgsec, 2),
        "unit": "img/sec/chip",
        "vs_baseline": round(fw_imgsec / raw_imgsec, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "keras_img_sec": round(keras_img_sec, 1),
        "keras_vs_baseline": round(keras_img_sec / keras_plain, 4),
        "transformer_tok_s": round(tfm_tok_s, 0),
    }


def cpu_reports() -> dict:
    """The chip-independent reports, each in CPU children of their own:
    simulated scaling efficiency, ZeRO byte accounting, live-reshard
    timing.  A failing report raises."""
    result = {"platform": "cpu"}
    eff = sim_scaling_efficiency()
    if eff is not None:
        median, spread, effs, ci, rejected, extras = eff
        # eff > 1.0 pairs were rejected inside the estimator, so the
        # trimmed median is already <= 1.0 by construction.
        result["scaling_eff_sim8"] = round(median, 4)
        result["scaling_eff_sim8_spread"] = round(spread, 4)
        result["scaling_eff_sim8_runs"] = [round(e, 4) for e in effs]
        result["scaling_eff_sim8_ci"] = [round(ci[0], 4),
                                         round(ci[1], 4)]
        result["scaling_eff_sim8_rejected"] = rejected
        if extras:
            # Collective-share decomposition under the overlap pipeline
            # (default) and the legacy barriered pipeline (before/after).
            result["sim8_collective_share"] = extras
    result["zero_bytes"] = zero_memory_report()
    result["reshard"] = reshard_report()
    return result


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--sim-child":
        run_sim_child(int(sys.argv[2]),
                      distributed="--no-dist" not in sys.argv)
    elif len(sys.argv) >= 3 and sys.argv[1] == "--zero-bytes-child":
        run_zero_bytes_child(int(sys.argv[2]))
    elif len(sys.argv) >= 2 and sys.argv[1] == "--reshard-child":
        run_reshard_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--chaos-child":
        run_chaos_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--chaos":
        main_chaos()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--autoscale-child":
        run_autoscale_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--autoscale":
        main_autoscale()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--obs-child":
        run_obs_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--obs":
        main_obs()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--cpu-reports":
        emit(cpu_reports())
    else:
        emit(run_bench())
