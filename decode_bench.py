"""Serving benchmark: continuous vs static batching on a seeded trace.

decode_bench.py is the load generator for the serving stack
(horovod_tpu/serve): it replays the SAME seeded mixed-length request
trace against two InferenceServers that differ ONLY in admission
policy — ``fifo`` (continuous batching: admit/evict per decode step)
vs ``static`` (wave batching: the whole batch drains before the next
wave boards) — and reports p50/p99 request latency, tokens/sec/chip,
batch occupancy, and KV-pool utilization for each, plus the speedup.

Each config runs in a fresh subprocess, so every config starts from an
empty jit cache and one child at a time owns the chip (the parent never
touches the backend).
One JSON line per config on stdout, human table on stderr, and a
machine-readable record appended to BENCH_serve.json (stale-gated
comparison against the previous record, docs/SERVING.md).

Usage:  python decode_bench.py            # real chip
        JAX_PLATFORMS=cpu python decode_bench.py --tiny   # smoke
"""

import argparse
import json
import os
import subprocess
import sys

# (tag, cfg_kwargs, quantize, max_batch, n_requests)
CONFIGS = [
    ("mha",        {},                      None,   8, 48),
    ("gqa4",       {"n_kv_heads": 2},       None,   8, 48),
    ("gqa4+int8",  {"n_kv_heads": 2},       "int8", 8, 48),
    ("b16",        {"n_kv_heads": 2},       None,  16, 96),
]

CHILD_CODE = r"""
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from horovod_tpu.common.util import configure_compile_cache

configure_compile_cache()
from horovod_tpu.models import TransformerConfig, transformer_init
from horovod_tpu.serve import InferenceServer
from horovod_tpu.serve.loadgen import make_trace, run_trace

kw = json.loads(sys.argv[1])
quantize = sys.argv[4] or None
max_batch, n_requests = int(sys.argv[2]), int(sys.argv[3])
d_model = 128 if {tiny!r} == "1" else 1024
layers = 2 if {tiny!r} == "1" else 8
cfg = TransformerConfig(
    vocab_size=512 if {tiny!r} == "1" else 8192,
    d_model=d_model, n_heads=d_model // 32, d_head=32,
    d_ff=4 * d_model, n_layers=layers,
    compute_dtype=jnp.float32 if {tiny!r} == "1" else None, **kw)
params = transformer_init(jax.random.PRNGKey(0), cfg)

# The realistic serving mix: mostly short answers plus a ~25% tail of
# long generations (bimodal budgets).  That tail is what wave batching
# wastes on — one long request pins every row of its wave — and what
# continuous batching's per-step evictions reclaim.
if {tiny!r} == "1":
    prompt_lens, lo, hi, llo, lhi = (4, 8), 2, 8, 40, 56
    max_seq = 8 + 56
else:
    prompt_lens, lo, hi, llo, lhi = (64, 128, 256), 16, 64, 192, 256
    max_seq = 256 + 256
trace = make_trace(7, n_requests, cfg.vocab_size,
                   prompt_lens=prompt_lens, max_new_lo=lo,
                   max_new_hi=hi, long_frac=0.25, long_lo=llo,
                   long_hi=lhi, arrival_every=0.5)

out = {{}}
for policy in ("fifo", "static"):
    # Replay 1 + 3 times on fresh servers: the first run absorbs every
    # prefill/step compile (the jit cache is process-wide) so policy
    # order can't bias the A/B through compilation; of the three timed
    # replays the FASTEST is reported (standard best-of-N — scheduler
    # noise only ever slows a run down).
    best = None
    for rep in range(4):
        srv = InferenceServer(params, cfg, max_seq_tokens=max_seq,
                              max_batch=max_batch, quantize=quantize,
                              policy=policy, seed=0)
        stats = run_trace(srv, trace)
        if rep and (best is None or stats["wall_s"] < best["wall_s"]):
            best = stats
    out[policy] = best
    del out[policy]["slo_decisions"]
out["speedup_tokens_per_sec"] = (
    out["fifo"]["tokens_per_sec_per_chip"]
    / out["static"]["tokens_per_sec_per_chip"])
print(json.dumps(out))
"""


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true",
                   help="small config / CPU smoke")
    p.add_argument("--out", default="BENCH_serve.json",
                   help="machine-readable record file (JSON lines)")
    args = p.parse_args()
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    from horovod_tpu.serve.loadgen import append_record, \
        read_latest_record
    prev = read_latest_record(os.path.join(repo, args.out))
    code = CHILD_CODE.format(repo=repo, tiny="1" if args.tiny else "0")
    records = {}
    for tag, kw, quantize, max_batch, n_requests in CONFIGS:
        if args.tiny:
            max_batch, n_requests = min(max_batch, 8), 48
        try:
            r = subprocess.run(
                [sys.executable, "-c", code, json.dumps(kw),
                 str(max_batch), str(n_requests), quantize or ""],
                capture_output=True, text=True, timeout=1800)
        except subprocess.TimeoutExpired:
            print(json.dumps({"config": tag, "error": "timeout"}),
                  flush=True)
            continue
        if r.returncode != 0:
            print(json.dumps({"config": tag,
                              "error": f"exit {r.returncode}"}),
                  flush=True)
            print(f"{tag}: {r.stderr[-300:]}", file=sys.stderr,
                  flush=True)
            continue
        res = json.loads(r.stdout.strip().splitlines()[-1])
        records[tag] = res
        print(json.dumps({"config": tag, "max_batch": max_batch,
                          **res}), flush=True)
        f, s = res["fifo"], res["static"]
        print(f"{tag:10s} continuous {f['tokens_per_sec_per_chip']:9.0f}"
              f" tok/s/chip (occ {f['batch_occupancy_mean']:4.2f}, "
              f"p99 {f['request_p99_ms']:7.1f} ms)  static "
              f"{s['tokens_per_sec_per_chip']:9.0f} tok/s/chip (occ "
              f"{s['batch_occupancy_mean']:4.2f})  speedup "
              f"{res['speedup_tokens_per_sec']:5.2f}x",
              file=sys.stderr, flush=True)
        # TTFT / inter-token percentiles come from the serving
        # histograms (hvd_serve_ttft_seconds / _intertoken_seconds),
        # delta-snapshotted per replay by run_trace.
        print(f"{'':10s} ttft p50/p99 "
              f"{f.get('ttft_p50_ms', 0.0):7.1f}/"
              f"{f.get('ttft_p99_ms', 0.0):7.1f} ms   itl p50/p99 "
              f"{f.get('itl_p50_ms', 0.0):6.2f}/"
              f"{f.get('itl_p99_ms', 0.0):6.2f} ms",
              file=sys.stderr, flush=True)
    if records:
        rec = {"bench": "decode_bench", "kind": "continuous_vs_static",
               "tiny": bool(args.tiny), "configs": records}
        if prev is not None and prev.get("bench") == "decode_bench" \
                and not prev.get("stale"):
            rec["vs_prev"] = {
                t: records[t]["fifo"]["tokens_per_sec_per_chip"]
                / prev["configs"][t]["fifo"]["tokens_per_sec_per_chip"]
                for t in records
                if t in prev.get("configs", {})}
        append_record(os.path.join(repo, args.out), rec)


if __name__ == "__main__":
    main()
