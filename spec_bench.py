"""Serving benchmark: speculative decoding under the SLO controller.

spec_bench.py is the second serving load generator (see
decode_bench.py for the continuous-vs-static A/B): it replays a seeded
trace against the continuous-batching InferenceServer three ways —
plain decode, forced speculative rounds (draft-propose / chunked
verify inside the serving loop), and SLO-toggled speculation
(HOROVOD_SERVE_SLO_MS semantics: spec flips on when observed per-token
p99 exceeds the target) — and reports p50/p99 latency, tokens/sec/chip
and, for the toggled run, the controller's decision trace.

With random weights an independent draft rarely agrees with the
target, so forced-spec numbers here are a LOWER bound; the self-draft
config shows the 100%-acceptance upper bound on round efficiency.
Each config runs in a fresh subprocess; one JSON line per
config on stdout, human table on stderr, machine-readable record
appended to BENCH_serve.json.

Usage:  python spec_bench.py            # real chip
        JAX_PLATFORMS=cpu python spec_bench.py --tiny   # smoke
"""

import argparse
import json
import os
import subprocess
import sys

# (tag, mode, draft, gamma, max_batch, n_requests)
#   mode: plain | spec (forced) | slo (controller-toggled)
#   draft: none | small | self
CONFIGS = [
    ("plain",    "plain", "none",  0, 8, 32),
    ("spec_g4",  "spec",  "small", 4, 8, 32),
    ("self_g4",  "spec",  "self",  4, 8, 32),
    ("slo_g4",   "slo",   "small", 4, 8, 32),
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

mode, draft, gamma, max_batch, n_requests = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]))
tiny = {tiny!r} == "1"
V = 512 if tiny else 8192

def cfg_for(d, L):
    return TransformerConfig(
        vocab_size=V, d_model=d, n_heads=max(1, d // 32), d_head=32,
        d_ff=4 * d, n_layers=L,
        compute_dtype=jnp.float32 if tiny else None)

cfg = cfg_for(64 if tiny else 1024, 2 if tiny else 8)
params = transformer_init(jax.random.PRNGKey(0), cfg)
dparams = dcfg = None
if draft == "self":
    dparams, dcfg = params, cfg
elif draft == "small":
    dcfg = cfg_for(32 if tiny else 256, 1 if tiny else 2)
    dparams = transformer_init(jax.random.PRNGKey(7), dcfg)

if tiny:
    prompt_lens, lo, hi, max_seq = (4, 8), 4, 16, 8 + 16
else:
    prompt_lens, lo, hi, max_seq = (64, 128), 32, 128, 128 + 128
trace = make_trace(11, n_requests, V, prompt_lens=prompt_lens,
                   max_new_lo=lo, max_new_hi=hi, arrival_every=1.0)

# SLO for the toggled run: half the plain per-token p50, so the
# controller genuinely engages speculation mid-run.
slo_ms = None
if mode == "slo":
    probe = InferenceServer(params, cfg, max_seq_tokens=max_seq,
                            max_batch=max_batch)
    probe_stats = run_trace(probe, trace)
    slo_ms = probe_stats["token_p50_ms"] * 0.5

srv = InferenceServer(
    params, cfg, max_seq_tokens=max_seq, max_batch=max_batch,
    draft_params=dparams, draft_cfg=dcfg,
    gamma=gamma if gamma else None, slo_ms=slo_ms,
    force_spec=(mode == "spec"))
stats = run_trace(srv, trace)
stats["spec_rounds"] = srv.spec_steps
if mode != "slo":
    del stats["slo_decisions"]
print(json.dumps(stats))
"""


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--out", default="BENCH_serve.json",
                   help="machine-readable record file (JSON lines)")
    args = p.parse_args()
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    from horovod_tpu.serve.loadgen import append_record
    code = CHILD_CODE.format(repo=repo, tiny="1" if args.tiny else "0")
    records = {}
    for tag, mode, draft, gamma, max_batch, n_requests in CONFIGS:
        if args.tiny:
            max_batch, n_requests = 4, 10
        try:
            r = subprocess.run(
                [sys.executable, "-c", code, mode, draft, str(gamma),
                 str(max_batch), str(n_requests)],
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
        print(json.dumps({"config": tag, **res}), flush=True)
        extra = f"  spec rounds {res['spec_rounds']}" \
            if res.get("spec_rounds") else ""
        if "slo_decisions" in res:
            extra += f"  slo flips {len(res['slo_decisions'])}"
        print(f"{tag:9s} {res['tokens_per_sec_per_chip']:9.0f} "
              f"tok/s/chip  tok p99 {res['token_p99_ms']:7.2f} ms  "
              f"req p99 {res['request_p99_ms']:8.1f} ms  "
              f"ttft p50/p99 {res.get('ttft_p50_ms', 0.0):6.1f}/"
              f"{res.get('ttft_p99_ms', 0.0):6.1f} ms  "
              f"itl p50/p99 {res.get('itl_p50_ms', 0.0):5.2f}/"
              f"{res.get('itl_p99_ms', 0.0):5.2f} ms{extra}",
              file=sys.stderr, flush=True)
    if records:
        append_record(os.path.join(repo, args.out),
                      {"bench": "spec_bench", "kind": "slo_speculative",
                       "tiny": bool(args.tiny), "configs": records})


if __name__ == "__main__":
    main()
