"""Long-sequence flash-attention sweep: Pallas flash vs XLA dense,
fwd+bwd wall time and peak-memory viability across T (the regime where
O(T) memory should also win wall-clock).

Each (path, T) runs in a fresh subprocess: a dense-attention OOM kills
one child, not the sweep, and the parent never touches the backend, so
one child at a time owns the chip.  Per-config batch shrinks as T grows
so total tokens stay comparable; H8 D64 bf16 causal matches the T=2048
measurement in docs/PERF_NOTES.md.

Output: one JSON line per config on stdout; human table on stderr.
Results feed docs/PERF_NOTES.md and pick the HOROVOD_FLASH_ATTENTION
default.
"""

import json
import os
import subprocess
import sys

# (T, B): constant-ish token count, B*T = 8192 tokens.
CONFIGS = [(2048, 4), (4096, 2), (8192, 1), (16384, 1), (32768, 1)]

CHILD_CODE = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from horovod_tpu.common.util import configure_compile_cache

configure_compile_cache()
path, T, B = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
H, D = 8, 64
q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D),
                             jnp.bfloat16) for i in range(3))

if path == "flash":
    from horovod_tpu.ops.flash_attention import flash_attention as attn
else:
    from horovod_tpu.parallel.sequence import dense_attention_oracle as attn


def loss(q, k, v):
    return jnp.sum(attn(q, k, v, causal=True).astype(jnp.float32))


step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


warmup, iters = 2, 5
for _ in range(warmup):
    g = step(q, k, v)
jax.block_until_ready(g)
t0 = time.perf_counter()
for _ in range(iters):
    g = step(q, k, v)
jax.block_until_ready(g)
dt = (time.perf_counter() - t0) / iters
print(json.dumps({{"ms_iter": dt * 1e3,
                   "tok_per_s": B * T / dt}}))
"""


def main():
    repo = os.path.dirname(os.path.abspath(__file__))
    code = CHILD_CODE.format(repo=repo)
    rows = {}
    for T, B in CONFIGS:
        for path in ("flash", "dense"):
            env = dict(os.environ)
            # The sweep times each path explicitly; keep routing flags out.
            env.pop("HOROVOD_FLASH_ATTENTION", None)
            try:
                r = subprocess.run(
                    [sys.executable, "-c", code, path, str(T), str(B)],
                    capture_output=True, text=True, timeout=900, env=env)
            except subprocess.TimeoutExpired:
                print(f"timeout: {path} T={T}", file=sys.stderr, flush=True)
                rows[(T, path)] = {"error": "timeout"}
                print(json.dumps({"T": T, "B": B, "path": path,
                                  "error": "timeout"}), flush=True)
                continue
            if r.returncode != 0:
                tail = r.stderr[-400:]
                kind = "oom" if ("RESOURCE_EXHAUSTED" in r.stderr
                                 or "Out of memory" in r.stderr) else "error"
                print(f"{kind}: {path} T={T}: {tail}",
                      file=sys.stderr, flush=True)
                rows[(T, path)] = {"error": kind}
                out = {"T": T, "B": B, "path": path, "error": kind}
                print(json.dumps(out), flush=True)
                continue
            res = json.loads(r.stdout.strip().splitlines()[-1])
            rows[(T, path)] = res
            out = {"T": T, "B": B, "path": path, **res}
            print(json.dumps(out), flush=True)
            print(f"T={T} B={B} {path}: {res['ms_iter']:.1f} ms/iter "
                  f"({res['tok_per_s']:.0f} tok/s)",
                  file=sys.stderr, flush=True)
    # Summary table: speedup where both paths ran.
    for T, B in CONFIGS:
        f, d = rows.get((T, "flash"), {}), rows.get((T, "dense"), {})
        if "ms_iter" in f and "ms_iter" in d:
            print(f"T={T}: flash {f['ms_iter']:.1f} ms vs dense "
                  f"{d['ms_iter']:.1f} ms -> {d['ms_iter']/f['ms_iter']:.3f}x",
                  file=sys.stderr, flush=True)
        elif "ms_iter" in f:
            print(f"T={T}: flash {f['ms_iter']:.1f} ms; dense "
                  f"{d.get('error', 'missing')}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
