"""Flash-attention block-size sweep: pick HOROVOD_FLASH_BLOCK_Q/K.

The kernel runs the score/output/gradient matmuls in the input dtype
(bf16 on the MXU) and its q/k block sizes are env-tunable; this sweep
measures fwd+bwd wall time across (T, bq, bk) combinations on the real
chip to pick shipping defaults and quantify the mixed-precision win vs
the earlier long-T sweep of the all-f32 kernel at 128x128.

Each config runs in a fresh subprocess (the block sizes are read from
the environment; the parent never touches the backend, so one child at
a time owns the chip).  One JSON line per config on stdout; human
summary on stderr.  Results feed docs/PERF_NOTES.md.
"""

import json
import os
import subprocess
import sys

# (T, B) x (bq, bk).  T=4096/8192 is the regime where the f32 kernel
# lost to XLA dense (0.89-0.95x); T=16384 is the only-flash regime.
CONFIGS = [(4096, 2), (8192, 1), (16384, 1)]
BLOCKS = [(128, 128), (256, 256), (512, 512), (256, 512),
          (512, 256), (128, 512), (1024, 512)]

CHILD_CODE = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from horovod_tpu.common.util import configure_compile_cache

configure_compile_cache()
import os
T, B, BQ, BK = (int(a) for a in sys.argv[1:5])
# The kernel reads tile sizes from env; set them from argv so a
# hand-rerun of this child command reproduces the same sweep point.
os.environ["HOROVOD_FLASH_BLOCK_Q"] = str(BQ)
os.environ["HOROVOD_FLASH_BLOCK_K"] = str(BK)
H, D = 8, 64
q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D),
                             jnp.bfloat16) for i in range(3))

from horovod_tpu.ops.flash_attention import flash_attention as attn


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
print(json.dumps({{"ms_iter": dt * 1e3, "tok_per_s": B * T / dt}}))
"""


def main():
    repo = os.path.dirname(os.path.abspath(__file__))
    code = CHILD_CODE.format(repo=repo)
    best = {}
    for T, B in CONFIGS:
        for bq, bk in BLOCKS:
            if T % bq or T % bk:
                continue
            env = dict(os.environ)
            env.pop("HOROVOD_FLASH_ATTENTION", None)
            env["HOROVOD_FLASH_BLOCK_Q"] = str(bq)
            env["HOROVOD_FLASH_BLOCK_K"] = str(bk)
            tag = f"T={T} bq={bq} bk={bk}"
            try:
                r = subprocess.run(
                    [sys.executable, "-c", code,
                     str(T), str(B), str(bq), str(bk)],
                    capture_output=True, text=True, timeout=900, env=env)
            except subprocess.TimeoutExpired:
                print(f"timeout: {tag}", file=sys.stderr, flush=True)
                print(json.dumps({"T": T, "B": B, "bq": bq, "bk": bk,
                                  "error": "timeout"}), flush=True)
                continue
            if r.returncode != 0:
                kind = ("oom" if "RESOURCE_EXHAUSTED" in r.stderr
                        else "error")
                print(f"{kind}: {tag}: {r.stderr[-300:]}",
                      file=sys.stderr, flush=True)
                print(json.dumps({"T": T, "B": B, "bq": bq, "bk": bk,
                                  "error": kind}), flush=True)
                continue
            res = json.loads(r.stdout.strip().splitlines()[-1])
            print(json.dumps({"T": T, "B": B, "bq": bq, "bk": bk, **res}),
                  flush=True)
            print(f"{tag}: {res['ms_iter']:.1f} ms/iter",
                  file=sys.stderr, flush=True)
            cur = best.get(T)
            if cur is None or res["ms_iter"] < cur[2]:
                best[T] = (bq, bk, res["ms_iter"])
    for T, (bq, bk, ms) in sorted(best.items()):
        print(f"best T={T}: bq={bq} bk={bk} at {ms:.1f} ms",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
