"""Catalog of every ``HOROVOD_*`` environment variable the codebase
reads or sets — the single source of truth the ``env-registry`` static
analyzer (scripts/hvdlint/envvars.py) enforces and ``docs/ENV_VARS.md``
is generated from (``python scripts/gen_env_docs.py``).

PURE STDLIB, no intra-package imports: the analyzer loads this file by
path on CI machines with no jax installed, so it must execute alone.

Conventions:

* ``util.getenv``-based reads also accept an ``HVD_TPU_`` alias prefix
  (``HOROVOD_<NAME>`` wins); the catalog lists the canonical name.
* ``dynamic_site`` marks entries whose reads are runtime-built names
  (the ``HOROVOD_[<SITE>_]RETRY_*`` family): the analyzer keeps them
  "live" as long as the named file still performs dynamic env reads.
* Adding a variable: declare it here FIRST, then read it in code, then
  regenerate the docs — the lint fails on any of the three drifting.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

__all__ = ["EnvVar", "CATALOG", "PREFIXES", "render_markdown"]


@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str          # human-readable default ("" = unset)
    component: str        # grouping key for the generated doc
    description: str
    doc: str = ""         # docs/<FILE>.md cross-link, "" = none
    dynamic_site: Optional[str] = None  # file building the name at runtime


def _v(name, default, component, description, doc="", dynamic_site=None):
    return EnvVar(name, default, component, description, doc, dynamic_site)


CATALOG: Tuple[EnvVar, ...] = (
    # -- topology / launcher contract ----------------------------------
    _v("HOROVOD_RANK", "0", "topology",
       "Global rank of this process; set by the launcher for every "
       "worker (reference: gloo_run's env contract).", "COMPONENTS.md"),
    _v("HOROVOD_SIZE", "1", "topology",
       "World size (total worker count) set by the launcher.",
       "COMPONENTS.md"),
    _v("HOROVOD_LOCAL_RANK", "0", "topology",
       "Rank of this process among workers on the same host.",
       "COMPONENTS.md"),
    _v("HOROVOD_LOCAL_SIZE", "1", "topology",
       "Number of workers on this host.", "COMPONENTS.md"),
    _v("HOROVOD_CROSS_RANK", "0", "topology",
       "Index of this worker's host among all hosts (cross-host rank).",
       "COMPONENTS.md"),
    _v("HOROVOD_CROSS_SIZE", "1", "topology",
       "Number of hosts participating in the job.", "COMPONENTS.md"),
    _v("HOROVOD_NUM_PROCESSES", "1", "topology",
       "jax.distributed world size used by hvd.init() when launched "
       "through horovodrun_tpu / Ray / Spark / LSF.", "COMPONENTS.md"),
    _v("HOROVOD_PROCESS_ID", "0", "topology",
       "jax.distributed process index of this worker.", "COMPONENTS.md"),
    _v("HOROVOD_COORDINATOR_ADDR", "(unset)", "topology",
       "host:port of the jax.distributed coordinator; presence selects "
       "the multi-process init path in hvd.init().", "COMPONENTS.md"),
    _v("HOROVOD_COORDINATOR_BASE_PORT", "(derived)", "topology",
       "Base port the elastic driver advances from when restarting the "
       "jax.distributed coordinator across generations.", "ELASTIC.md"),
    _v("HOROVOD_HOSTNAME", "(os hostname)", "topology",
       "Logical host name override used for elastic slot attribution "
       "and host-scoped fault injection.", "ELASTIC.md"),
    _v("HOROVOD_SLOT", "(unset)", "topology",
       "Elastic slot index assigned to this worker by the driver.",
       "ELASTIC.md"),

    # -- launcher compat / forwarding ----------------------------------
    _v("HOROVOD_CONTROLLER", "xla", "launcher",
       "Controller implementation advertised to workers (reference "
       "parity knob; always 'xla' here).", "MIGRATION.md"),
    _v("HOROVOD_CPU_OPERATIONS", "xla", "launcher",
       "CPU collective implementation advertised to workers (reference "
       "parity knob; always 'xla' here).", "MIGRATION.md"),
    _v("HOROVOD_CYCLE_TIME", "(unset)", "launcher",
       "Forwarded from `horovodrun_tpu --cycle-time-ms` (reference "
       "background-loop cadence; informational on TPU).",
       "MIGRATION.md"),
    _v("HOROVOD_CACHE_CAPACITY", "(unset)", "launcher",
       "Forwarded from `horovodrun_tpu --cache-capacity` (reference "
       "response-cache size; informational on TPU).", "MIGRATION.md"),
    _v("HOROVOD_LOG_LEVEL", "(unset)", "launcher",
       "Worker log level forwarded from `horovodrun_tpu --log-level`.",
       "COMPONENTS.md"),

    # -- rendezvous ------------------------------------------------------
    _v("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1", "rendezvous",
       "Address of the launcher's rendezvous/KV server workers connect "
       "back to.", "COMPONENTS.md"),
    _v("HOROVOD_RENDEZVOUS_PORT", "(assigned)", "rendezvous",
       "Port of the rendezvous/KV server.", "COMPONENTS.md"),
    _v("HOROVOD_SECRET_KEY", "(generated)", "rendezvous",
       "Shared HMAC secret authenticating every rendezvous/KV request.",
       "COMPONENTS.md"),

    # -- elastic ---------------------------------------------------------
    _v("HOROVOD_ELASTIC", "0", "elastic",
       "Set to 1 by the elastic driver: workers run the elastic "
       "commit/restore protocol.", "ELASTIC.md"),
    _v("HOROVOD_ELASTIC_GEN", "0", "elastic",
       "Elastic generation counter; bumped by the driver on every "
       "membership change, checked by collective consistency guards.",
       "ELASTIC.md"),
    _v("HOROVOD_ELASTIC_JOINING", "0", "elastic",
       "1 for a worker joining an already-running generation (restores "
       "state from peers before stepping).", "ELASTIC.md"),
    _v("HOROVOD_ELASTIC_LEASE_TTL", "15.0", "elastic",
       "Seconds a worker heartbeat lease lives; the driver fails "
       "hung-but-alive workers whose lease lapses.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_HEARTBEAT_INTERVAL", "lease_ttl/3 (min 0.5)", "elastic",
       "Seconds between worker heartbeat-lease publishes; defaults to a "
       "third of HOROVOD_ELASTIC_LEASE_TTL.", "FAULT_TOLERANCE.md"),
    _v("HOROVOD_BLACKLIST_THRESHOLD", "1", "elastic",
       "Failure strikes before a host is blacklisted from respawn.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_RESPAWN_BACKOFF_BASE", "1.0", "elastic",
       "Base seconds of the exponential respawn backoff per host.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_RESPAWN_BACKOFF_MAX", "30.0", "elastic",
       "Cap in seconds of the exponential respawn backoff.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_CKPT_QUARANTINE_KEEP", "3", "elastic",
       "Newest `.corrupt` quarantined checkpoint directories kept for "
       "forensics; older ones are pruned (0 keeps none).",
       "FAULT_TOLERANCE.md"),

    # -- fault injection / retries --------------------------------------
    _v("HOROVOD_FAULT_SPEC", "(unset)", "faults",
       "Deterministic fault-injection schedule, e.g. "
       "`rendezvous.put:err:0.1,collective.allreduce:delay:50ms`.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_FAULT_SEED", "0", "faults",
       "Seed for the fault-injection RNG; a given seed replays the "
       "exact same fault sequence.", "FAULT_TOLERANCE.md"),
    _v("HOROVOD_FAULT_HOSTS", "(all)", "faults",
       "Comma-separated hosts the fault spec applies to.",
       "FAULT_TOLERANCE.md"),
    _v("HOROVOD_CHAOS_GENERATIONS", "8", "faults",
       "Analysis-window generations one chaos soak runs "
       "(faults/chaos.py; each generation ends in a merged-trace "
       "window + digest check).", "CHAOS.md"),
    _v("HOROVOD_CHAOS_STEPS_PER_GEN", "6", "faults",
       "Training steps per chaos-soak generation.", "CHAOS.md"),
    _v("HOROVOD_RETRY_MAX_ATTEMPTS", "5", "faults",
       "Attempts for the shared RetryPolicy (global default; "
       "`HOROVOD_<SITE>_RETRY_MAX_ATTEMPTS` overrides per site, e.g. "
       "RENDEZVOUS, RESET).", "FAULT_TOLERANCE.md"),
    _v("HOROVOD_RETRY_BASE_DELAY", "0.5", "faults",
       "Initial backoff seconds of the shared RetryPolicy "
       "(`HOROVOD_<SITE>_RETRY_BASE_DELAY` overrides per site).",
       "FAULT_TOLERANCE.md",
       dynamic_site="horovod_tpu/faults/retry.py"),
    _v("HOROVOD_RETRY_MAX_DELAY", "30.0", "faults",
       "Backoff cap in seconds (`HOROVOD_<SITE>_RETRY_MAX_DELAY` "
       "overrides per site).", "FAULT_TOLERANCE.md",
       dynamic_site="horovod_tpu/faults/retry.py"),
    _v("HOROVOD_RETRY_MULTIPLIER", "2.0", "faults",
       "Exponential backoff multiplier (`HOROVOD_<SITE>_RETRY_"
       "MULTIPLIER` overrides per site).", "FAULT_TOLERANCE.md",
       dynamic_site="horovod_tpu/faults/retry.py"),
    _v("HOROVOD_RETRY_JITTER", "0.1", "faults",
       "Jitter fraction added to each backoff delay "
       "(`HOROVOD_<SITE>_RETRY_JITTER` overrides per site).",
       "FAULT_TOLERANCE.md",
       dynamic_site="horovod_tpu/faults/retry.py"),
    _v("HOROVOD_RETRY_DEADLINE", "(none)", "faults",
       "Wall-clock seconds budget for the whole retry loop "
       "(`HOROVOD_<SITE>_RETRY_DEADLINE` overrides per site).",
       "FAULT_TOLERANCE.md",
       dynamic_site="horovod_tpu/faults/retry.py"),

    # -- metrics / stall watchdog ---------------------------------------
    _v("HOROVOD_METRICS_DISABLE", "0", "metrics",
       "1 disables all metric recording (hot paths skip the registry "
       "entirely).", "METRICS.md"),
    _v("HOROVOD_METRICS_PORT", "-1", "metrics",
       "Port for the Prometheus exposition endpoint; -1 disables, 0 "
       "picks a free port.", "METRICS.md"),
    _v("HOROVOD_METRICS_KV_INTERVAL", "5.0", "metrics",
       "Seconds between KV fleet-view snapshot publishes from the "
       "stall watchdog thread.", "METRICS.md"),
    _v("HOROVOD_STALL_CHECK_DISABLE", "0", "metrics",
       "1 disables the stall inspector watchdog.", "METRICS.md"),
    _v("HOROVOD_STALL_CHECK_TIME_SECONDS", "60.0", "metrics",
       "Seconds a collective must be outstanding before a stall "
       "warning (reference: stall_inspector.cc).", "METRICS.md"),
    _v("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", "0.0", "metrics",
       "Seconds after which a stalled job aborts; 0 disables shutdown.",
       "METRICS.md"),
    _v("HOROVOD_METRICS_HISTORY_INTERVAL", "0 (off)", "metrics",
       "Seconds between background history-ring samples of every "
       "metric series (metrics/history.py); 0/unset disables the "
       "sampler.", "TELEMETRY.md"),
    _v("HOROVOD_METRICS_HISTORY_DEPTH", "512", "metrics",
       "Points kept per series ring before the oldest are evicted.",
       "TELEMETRY.md"),
    _v("HOROVOD_METRICS_HISTORY_DIR", "(system temp)", "metrics",
       "Directory for the history JSONL dumps written on "
       "flight-recorder triggers.", "TELEMETRY.md"),
    _v("HOROVOD_SLO_BUDGET_TARGET", "0.99", "metrics",
       "Availability target of an SLO error budget (metrics/budget.py); "
       "0.99 means 1% of events may be bad before the budget is spent.",
       "TELEMETRY.md"),
    _v("HOROVOD_SLO_BUDGET_WINDOW", "3600", "metrics",
       "Seconds of history one error budget is computed over.",
       "TELEMETRY.md"),
    _v("HOROVOD_SLO_BUDGET_FAST", "60", "metrics",
       "Fast burn-rate window seconds (page when fast AND slow burn "
       "both exceed 1x — the multi-window SRE rule).", "TELEMETRY.md"),
    _v("HOROVOD_SLO_BUDGET_SLOW", "600", "metrics",
       "Slow burn-rate window seconds.", "TELEMETRY.md"),
    _v("HOROVOD_SLO_STEP_MS", "(unset)", "metrics",
       "Training step-time SLO threshold in ms; setting it arms a "
       "train_step error budget in the chaos soak / training loop.",
       "TELEMETRY.md"),
    _v("HOROVOD_ANOMALY_Z", "4.0", "metrics",
       "EWMA z-score threshold for the anomaly detectors "
       "(metrics/anomaly.py); higher = fewer, louder trips.",
       "TELEMETRY.md"),

    # -- timeline --------------------------------------------------------
    _v("HOROVOD_TIMELINE", "(unset)", "timeline",
       "Path of the Chrome-trace timeline file; setting it enables the "
       "timeline.", "TIMELINE.md"),
    _v("HOROVOD_TIMELINE_ALL_RANKS", "0", "timeline",
       "1 records a timeline on every rank instead of rank 0 only.",
       "TIMELINE.md"),
    _v("HOROVOD_TIMELINE_MARK_CYCLES", "0", "timeline",
       "1 marks step/cycle boundaries in the timeline.", "TIMELINE.md"),
    _v("HOROVOD_TIMELINE_DISABLE_NATIVE", "0", "timeline",
       "1 forces the pure-Python timeline writer (skips the native C++ "
       "buffered writer).", "TIMELINE.md"),

    # -- fleet tracer (horovod_tpu/trace) --------------------------------
    _v("HOROVOD_TRACE_STEP_SPANS", "1", "trace",
       "1 emits one per-step host span (ph=X, cat=step) per dispatched "
       "data_parallel step when the timeline is active — the record the "
       "fleet tracer's critical-path analysis consumes.", "TRACE.md"),
    _v("HOROVOD_TRACE_ALIGN", "cycle", "trace",
       "Cross-rank clock alignment for trace merge/analyze: 'cycle' "
       "aligns ranks on the CYCLE_n per-step barrier instants, 'wall' "
       "trusts the raw per-rank clocks.", "TRACE.md"),
    _v("HOROVOD_TRACE_FLOW_EVENTS", "1", "trace",
       "1 links the same collective across ranks with Chrome flow "
       "events (s/t/f) in the merged fleet trace.", "TRACE.md"),
    _v("HOROVOD_STRAGGLER_PATIENCE", "3", "trace",
       "Consecutive analysis windows one rank must be blamed before "
       "the straggler reaction policy acts (trace/reaction.py).",
       "CHAOS.md"),
    _v("HOROVOD_STRAGGLER_SKEW_THRESHOLD", "0.75", "trace",
       "Skew share (straggler wait / critical path) at or above which "
       "the reaction escalates straight to graceful degradation "
       "instead of a bucket rebalance.", "CHAOS.md"),
    _v("HOROVOD_STRAGGLER_COOLDOWN", "2", "trace",
       "Analysis windows the reaction policy sleeps after firing, so "
       "post-reaction windows measure the settled fleet before a new "
       "blame streak can build.", "CHAOS.md"),

    # -- autotune / gradient pipeline -----------------------------------
    _v("HOROVOD_AUTOTUNE", "0", "autotune",
       "1 enables the online autotuner (fusion threshold, bucket "
       "order, min buckets).", "AUTOTUNE.md"),
    _v("HOROVOD_AUTOTUNE_LOG", "(unset)", "autotune",
       "CSV file the autotuner appends per-sample rates/values to.",
       "AUTOTUNE.md"),
    _v("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "3", "autotune",
       "Samples discarded before the autotuner starts scoring.",
       "AUTOTUNE.md"),
    _v("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "10", "autotune",
       "Steps aggregated into one autotuner throughput sample.",
       "AUTOTUNE.md"),
    _v("HOROVOD_AUTOTUNE_MAX_SAMPLES", "40", "autotune",
       "Sample budget after which the autotuner freezes the best "
       "configuration.", "AUTOTUNE.md"),
    _v("HOROVOD_FUSION_THRESHOLD", "67108864", "autotune",
       "Gradient-fusion bucket size in bytes (reference: "
       "HOROVOD_FUSION_THRESHOLD).", "AUTOTUNE.md"),
    _v("HOROVOD_MIN_BUCKETS", "1", "autotune",
       "Lower bound on gradient buckets per step (overlap-aware "
       "pipeline).", "AUTOTUNE.md"),
    _v("HOROVOD_BUCKET_ORDER", "reverse", "autotune",
       "Gradient bucketing order: reverse (availability order), "
       "forward, or a comma permutation.", "AUTOTUNE.md"),
    _v("HOROVOD_SHARD_AG_FUSION", "0", "autotune",
       "1 fuses the sharded-optimizer param allgathers into one "
       "collective (0 overlaps per-group gathers).", "AUTOTUNE.md"),
    _v("HOROVOD_WIRE_THRESHOLD", "1048576", "autotune",
       "Byte threshold above which the wire policy routes a bucket to "
       "its big (quantized) codec; autotunable.", "WIRE.md"),
    _v("HOROVOD_WIRE_BIG_FORMAT", "int8", "autotune",
       "Codec the wire policy's auto mode assigns to big buckets; "
       "autotunable as `wire_big_format` (per-bucket-class format "
       "search).", "WIRE.md"),
    _v("HOROVOD_FUSED_CHUNK_BYTES", "1048576", "autotune",
       "Chunk size of the fused computation-collective software "
       "pipeline; autotunable as `fused_chunk_bytes`.",
       "FUSED_COLLECTIVES.md"),

    # -- training-health guardian ---------------------------------------
    _v("HOROVOD_GUARD", "0", "guard",
       "1 arms the training-health guardian in the distributed "
       "optimizer: fused non-finite sentinel plus coordinated "
       "skip-step.", "GUARD.md"),
    _v("HOROVOD_GUARD_LOSS_SCALE", "(unset)", "guard",
       "Initial dynamic loss scale (e.g. 65536).  Unset keeps a static "
       "scale of 1.0: skip-step only, bitwise-identical clean steps.",
       "GUARD.md"),
    _v("HOROVOD_GUARD_GROWTH_INTERVAL", "2000", "guard",
       "Clean applies before the dynamic loss scale doubles; "
       "autotunable as `loss_scale_growth_interval`.", "GUARD.md"),
    _v("HOROVOD_GUARD_DIGEST_INTERVAL", "100", "guard",
       "Steps between cross-replica parameter-digest divergence checks "
       "(0 disables); autotunable as `guard_digest_interval`.",
       "GUARD.md"),
    _v("HOROVOD_GUARD_MAX_NONFINITE", "3", "guard",
       "Consecutive non-finite steps tolerated before the guardian "
       "escalates to checkpoint rollback.", "GUARD.md"),

    # -- collectives / ops ----------------------------------------------
    _v("HOROVOD_HIERARCHICAL_ALLREDUCE", "0", "ops",
       "1 routes multi-slice allreduce through ICI reduce-scatter -> "
       "DCN allreduce -> ICI all-gather (reference knob name).",
       "PERF_NOTES.md"),
    _v("HOROVOD_HIERARCHICAL_DCN_WIRE", "(exact)", "ops",
       "Wire format of the DCN leg of hierarchical allreduce: any "
       "registered codec (none/fp16/bf16/int8/int4/fp8_*).", "WIRE.md"),
    _v("HOROVOD_WIRE_POLICY", "(unset)", "ops",
       "Per-bucket wire-format policy for gradient reductions: auto, "
       "exact, or big=<codec>,small=<codec>[,threshold=<bytes>].",
       "WIRE.md"),
    _v("HOROVOD_SHARD_OPTIMIZER", "0", "ops",
       "1 enables the ZeRO-1 sharded-optimizer path: reduce-scatter "
       "gradients, shard-local optax update, param allgather.",
       "SHARDED_OPTIMIZER.md"),
    _v("HOROVOD_SHARD_AG_WIRE", "(exact)", "ops",
       "Low-precision wire of the sharded param allgather: any "
       "registered codec (fp32 masters stay exact on the owner).",
       "SHARDED_OPTIMIZER.md"),
    _v("HOROVOD_ZERO_STAGE", "0 (1 if HOROVOD_SHARD_OPTIMIZER)", "ops",
       "ZeRO ladder rung 0..3: 1 shards optimizer state, 2 adds "
       "gradient-sharded accumulation, 3 adds parameter sharding via "
       "zero3_placement (autotunable).", "SHARDED_OPTIMIZER.md"),
    _v("HOROVOD_ZERO_GATHER_WIRE", "(exact)", "ops",
       "Wire format of the ZeRO-3 just-in-time param bucket allgather: "
       "any registered codec (shards at rest stay exact).",
       "SHARDED_OPTIMIZER.md"),
    _v("HOROVOD_COLLECTIVE_CONSISTENCY_CHECK", "0", "ops",
       "1 enables the cross-rank shape/dtype/generation consistency "
       "guard around collectives.", "FAULT_TOLERANCE.md"),
    _v("HOROVOD_CONSISTENCY_TIMEOUT", "30.0", "ops",
       "Seconds the consistency check waits for peers' collective "
       "signatures before declaring them divergent/stalled (read per "
       "check).", "FAULT_TOLERANCE.md"),
    _v("HOROVOD_JOIN_MODE", "0", "ops",
       "1 arms hvd.join() semantics: ranks that exhausted data "
       "contribute masked zeros.", "PROCESS_SETS.md"),
    _v("HOROVOD_FUSED_COLLECTIVES", "0", "ops",
       "1 routes bucket reductions and the ZeRO-1 scatter/gather pair "
       "through the chunked fused computation-collective pipeline.",
       "FUSED_COLLECTIVES.md"),
    _v("HOROVOD_FUSED_PALLAS", "0", "ops",
       "1 runs the fused pipeline's matmul chunks through the tiled "
       "Pallas kernel instead of the XLA dot decomposition.",
       "FUSED_COLLECTIVES.md"),
    _v("HOROVOD_ADASUM_PALLAS", "0", "ops",
       "1 routes Adasum dot/norm/scaled-add through the fused Pallas "
       "kernels.", "ADASUM.md"),
    _v("HOROVOD_FLASH_ATTENTION", "0", "ops",
       "1 enables the Pallas flash-attention kernel in ring/sequence "
       "parallel attention.", "PERF_NOTES.md"),
    _v("HOROVOD_FLASH_ATTENTION_MIN_T", "16384", "ops",
       "Minimum sequence length before flash attention auto-engages on "
       "TPU.", "PERF_NOTES.md"),
    _v("HOROVOD_FLASH_BLOCK_Q", "128", "ops",
       "Flash-attention query block rows.", "PERF_NOTES.md"),
    _v("HOROVOD_FLASH_BLOCK_K", "128", "ops",
       "Flash-attention key/value block rows.", "PERF_NOTES.md"),

    # -- models ----------------------------------------------------------
    _v("HOROVOD_CONV0_SPACE_TO_DEPTH", "auto (TPU: 1)", "models",
       "Space-to-depth transform of the ResNet stem conv; exact "
       "rewrite, default on when an MXU is present.", "PERF_NOTES.md"),

    # -- bench harness ---------------------------------------------------
    _v("HOROVOD_BENCH_BATCH", "0 (auto)", "bench",
       "Global batch override for bench.py (0 = 256).",
       "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_MEGASTEP", "8", "bench",
       "Megastep k for bench.py timing (1 restores one dispatch per "
       "step).", "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_LEGACY_PIPELINE", "0", "bench",
       "1 restores the pre-overlap barriered gradient pipeline for A/B "
       "runs.", "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_SIM_RUNS", "7", "bench",
       "Repetitions of each simulated-scaling bench point.",
       "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_SIM_MAX_RUNS", "9", "bench",
       "Cap on adaptive extra repetitions of noisy bench points.",
       "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_XLA_FLAGS", "(unset)", "bench",
       "Extra XLA_FLAGS appended for bench.py child processes.",
       "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_CACHE_MAX_AGE_H", "24", "bench",
       "Hours after which the previous record in a BENCH_*.json file is "
       "marked stale and not compared against.",
       "BENCHMARKS.md"),
    _v("HOROVOD_BENCH_CHAOS_NP", "2", "bench",
       "Fleet size of the `bench.py --chaos` fault-loaded soak "
       "(BENCH_chaos.json MTTR record).",
       "CHAOS.md"),
    _v("HOROVOD_SERVE_PAGE_TOKENS", "16", "serve",
       "KV-cache pool page size in tokens (autotuner knob "
       "serve_page_tokens; compiled-shape key of the serving step).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_MAX_BATCH", "8", "serve",
       "Row count of the compiled continuous-batching decode step "
       "(autotuner knob serve_max_batch).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_POOL_PAGES", "0", "serve",
       "KV pool size in pages; 0 = auto (max_batch full-length "
       "sequences).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_SLO_MS", "(unset)", "serve",
       "Per-token p99 latency SLO in ms; when observed p99 exceeds it "
       "the server flips speculative decoding on (unset/0 disables the "
       "controller).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_REPLICA_ID", "(set by ReplicaManager)", "serve",
       "Replica index handed to each `python -m "
       "horovod_tpu.serve.replica` worker by its manager (internal "
       "spawn handshake, like the rendezvous address/port).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_SPEC_GAMMA", "4", "serve",
       "Speculative draft length per serving round (autotuner knob "
       "serve_spec_gamma; compiled verify-chunk width).",
       "SERVING.md"),
    _v("HOROVOD_SERVE_METRICS_INTERVAL", "16", "serve",
       "Steps between serving-gauge samples (queue depth, occupancy, "
       "pool pages, p99); a final unconditional flush runs at drain "
       "and atexit so shorter runs still report.",
       "SERVING.md"),
    _v("HOROVOD_SERVE_FLIGHTREC_DEPTH", "512", "serve",
       "Flight-recorder ring depth in events (autotuner knob "
       "serve_flightrec_depth, host_only: never part of the "
       "program-cache key); <= 0 disables the recorder.",
       "SERVING.md"),
    _v("HOROVOD_SERVE_FLIGHTREC_DIR", "$TMPDIR/horovod_flightrec", "serve",
       "Directory flight-recorder dumps are written to on a trigger "
       "(crash, pool exhaustion, SLO breach, guard escalation, "
       "injected replica death).  Defaults under the system temp dir "
       "so crash dumps never land in (and get committed from) the "
       "working tree.",
       "SERVING.md"),
    _v("HOROVOD_AUTOSCALE_MIN_REPLICAS", "1", "serve",
       "Floor of the autoscaled decode fleet; shrink never retires "
       "below it (the budget latch additionally forbids any shrink "
       "while the SLO budget is breaching).",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_MAX_REPLICAS", "8", "serve",
       "Ceiling of the autoscaled decode fleet; pressure beyond it "
       "walks the degrade ladder instead (borrow training chips, then "
       "priority shed).",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_COOLDOWN", "32", "serve",
       "Observations after a scale event during which no further "
       "event fires; reversals wait twice as long (anti-flap). "
       "Autotuner knob autoscale_cooldown, host_only.",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_DWELL", "8", "serve",
       "Consecutive observations a pressure/relief condition must "
       "persist before a scale event fires (the hysteresis dwell, "
       "same idea as the SLO controller's). Autotuner knob "
       "autoscale_dwell, host_only.",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_OCC_HIGH", "0.85", "serve",
       "Occupancy high watermark: sustained occupancy at or above it "
       "WITH a backlog is scale-up pressure.",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_OCC_LOW", "0.30", "serve",
       "Occupancy low watermark: sustained occupancy at or below it "
       "with an empty queue and a healthy error budget is scale-down "
       "relief.",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_QUEUE_MS", "1000", "serve",
       "Head-of-line queue-wait threshold in ms; the oldest queued "
       "request waiting past it is scale-up pressure regardless of "
       "occupancy (0 disables the signal).",
       "AUTOSCALE.md"),
    _v("HOROVOD_AUTOSCALE_TENANT_CLASSES", "premium:0,standard:1,batch:2",
       "serve",
       "Tenant SLO classes as name:priority pairs (lower = more "
       "important); priority load-shedding drops the highest-number "
       "class first, newest requests first.",
       "AUTOSCALE.md"),
    _v("HOROVOD_RESHARD_PEAK_BYTES", "67108864", "reshard",
       "Per-host staging ceiling of a live reshard in bytes; chunks "
       "are sized to at most a quarter of it and the measured peak is "
       "asserted against it (hvd_reshard_peak_bytes).",
       "RESHARD.md"),
    _v("HOROVOD_RESHARD_CHUNK_BYTES", "0", "reshard",
       "Reshard chunk-grid cell size in bytes; 0 = auto (autotuner "
       "knob reshard_chunk_bytes, 4 MiB default), always clamped to "
       "PEAK_BYTES/4.",
       "RESHARD.md"),
    _v("HOROVOD_RESHARD_WIRE", "none", "reshard",
       "Wire format of reshard chunk payloads: none (exact, the "
       "bitwise default) or a cast wire (bf16/fp16) when the handoff "
       "tolerates precision loss (train-to-serve).",
       "RESHARD.md"),
    _v("HOROVOD_RESHARD_TIMEOUT", "60", "reshard",
       "Seconds a reshard fetch waits for a peer's chunk or verdict "
       "before declaring the peer dead and falling back to the "
       "checkpoint-restore path.",
       "RESHARD.md"),
)

#: Literal prefixes that legitimately appear in code (startswith filters
#: and env-forwarding serializers), not concrete variable reads.
PREFIXES: Dict[str, str] = {
    "HOROVOD_": "env-forwarding filters (ssh/LSF/Spark serialization, "
                "util.getenv's accepted-prefix list) and f-string "
                "construction of catalogued names",
}

_COMPONENT_ORDER = (
    "topology", "launcher", "rendezvous", "elastic", "faults",
    "metrics", "timeline", "trace", "autotune", "guard", "ops",
    "models", "serve", "bench",
)

_HEADER = """\
# Environment variables

<!-- GENERATED FILE — do not edit by hand.
     Source of truth: horovod_tpu/common/env_catalog.py
     Regenerate:      python scripts/gen_env_docs.py
     Enforced by:     scripts/lint_all.py (env-registry analyzer) -->

Every `HOROVOD_*` variable the codebase reads or sets.  `util.getenv`
-based reads also accept the `HVD_TPU_` alias prefix (the `HOROVOD_`
spelling wins when both are set).  The site-scoped retry family
`HOROVOD_<SITE>_RETRY_{MAX_ATTEMPTS,BASE_DELAY,MAX_DELAY,MULTIPLIER,
JITTER,DEADLINE}` (sites: `RENDEZVOUS`, `REGISTRATION`, `RESET`, ...)
overrides the global `HOROVOD_RETRY_*` defaults per call site — see
[FAULT_TOLERANCE.md](FAULT_TOLERANCE.md).

See [STATIC_ANALYSIS.md](STATIC_ANALYSIS.md) for how the `env-registry`
analyzer keeps this table, the catalog, and the code in sync.
"""


def render_markdown() -> str:
    """docs/ENV_VARS.md content, deterministically, from CATALOG."""
    out = [_HEADER]
    by_comp: Dict[str, list] = {}
    for v in CATALOG:
        by_comp.setdefault(v.component, []).append(v)
    comps = list(_COMPONENT_ORDER) + sorted(
        set(by_comp) - set(_COMPONENT_ORDER))
    for comp in comps:
        entries = by_comp.get(comp)
        if not entries:
            continue
        out.append(f"\n## {comp}\n")
        out.append("| variable | default | description | doc |")
        out.append("|---|---|---|---|")
        for v in sorted(entries, key=lambda e: e.name):
            doc = f"[{v.doc}]({v.doc})" if v.doc else ""
            out.append(f"| `{v.name}` | `{v.default}` | "
                       f"{v.description} | {doc} |")
    out.append("")
    return "\n".join(out)


if __name__ == "__main__":
    print(render_markdown(), end="")
