"""Single-chip MFU sweep: batch size × conv0 space-to-depth × input
dtype × XLA scheduler flags, on the ResNet-50 headline config.

Run on the accelerator (`python bench_sweep.py`); each configuration
executes in a fresh subprocess — XLA_FLAGS and the space-to-depth knob
are read at process start, and the parent never touches the backend, so
one child at a time owns the chip — and reports img/s/chip.  Results
feed docs/PERF_NOTES.md and pick the defaults bench.py ships with
(the named levers are input layout at 224px and the host→HBM pipeline;
conv0 space-to-depth is the layout lever).

Output: one JSON line per config on stdout; human table on stderr.
"""

import itertools
import json
import os
import subprocess
import sys

CONFIGS = []
for batch, s2d in itertools.product((128, 256, 512), (0, 1)):
    CONFIGS.append({"batch": batch, "s2d": s2d, "flags": ""})
# XLA latency-hiding scheduler sweep on the best-known batch.
for flags in (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
):
    CONFIGS.append({"batch": 256, "s2d": 1, "flags": flags})

CHILD_CODE = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, optax
import horovod_tpu as hvd
from horovod_tpu.models import resnet_init
from bench import build_step, time_steps

hvd.init()
batch = int(sys.argv[1])
image = 224
rng = jax.random.PRNGKey(42)
v = resnet_init(rng, 50, num_classes=1000)
opt = optax.sgd(0.0125, momentum=0.9)
x = jax.random.normal(jax.random.PRNGKey(0), (batch, image, image, 3),
                      jnp.bfloat16).astype(jnp.float32)
y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)
state = {{"params": v["params"], "batch_stats": v["batch_stats"]}}
opt_state = opt.init(state["params"])
step = hvd.data_parallel(build_step(opt, v["config"], distributed=True))
sb = hvd.shard_batch((x, y))
t, _, _ = time_steps(step, state, opt_state, sb, warmup=5, iters=20)
print(json.dumps({{"img_sec_per_chip": batch / t / hvd.size(),
                   "ms_step": t * 1e3}}))
"""


def main():
    repo = os.path.dirname(os.path.abspath(__file__))
    code = CHILD_CODE.format(repo=repo)
    results = []
    for cfg in CONFIGS:
        env = dict(os.environ)
        env["HOROVOD_CONV0_SPACE_TO_DEPTH"] = str(cfg["s2d"])
        if cfg["flags"]:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " " + cfg["flags"]).strip()
        try:
            r = subprocess.run(
                [sys.executable, "-c", code, str(cfg["batch"])],
                capture_output=True, text=True, timeout=600, env=env)
        except subprocess.TimeoutExpired:
            print(f"timeout: {cfg}", file=sys.stderr, flush=True)
            continue
        if r.returncode != 0:
            print(f"failed: {cfg}: {r.stderr[-300:]}",
                  file=sys.stderr, flush=True)
            continue
        out = json.loads(r.stdout.strip().splitlines()[-1])
        row = {**cfg, **out}
        results.append(row)
        print(json.dumps(row), flush=True)
        print(f"batch={cfg['batch']} s2d={cfg['s2d']} "
              f"flags='{cfg['flags']}' -> "
              f"{out['img_sec_per_chip']:.1f} img/s/chip "
              f"({out['ms_step']:.1f} ms)", file=sys.stderr, flush=True)
    if results:
        best = max(results, key=lambda r: r["img_sec_per_chip"])
        print(f"best: {best}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
