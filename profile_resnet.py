"""Capture a jax.profiler device trace of the headline ResNet-50 step
and print the top time-consuming XLA ops — the measurement behind the
single-chip MFU work (find the layout/pipeline bottleneck before
building kernels for it).

Usage: python profile_resnet.py [batch] (defaults 256; set
HOROVOD_CONV0_SPACE_TO_DEPTH etc. externally to profile variants).
Prints a per-op-category summary table on stderr and writes the raw
trace under ./prof_resnet/.
"""

import glob
import gzip
import json
import os
import sys


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet_init
    from bench import build_step, time_steps

    hvd.init()
    image = 224
    v = resnet_init(jax.random.PRNGKey(42), 50, num_classes=1000)
    opt = optax.sgd(0.0125, momentum=0.9)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, image, image, 3),
                          jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)
    state = {"params": v["params"], "batch_stats": v["batch_stats"]}
    opt_state = opt.init(state["params"])
    step = hvd.data_parallel(build_step(opt, v["config"], distributed=True))
    sb = hvd.shard_batch((x, y))

    # Warm + compile outside the trace.
    t, state, opt_state = time_steps(step, state, opt_state, sb,
                                     warmup=3, iters=5)
    print(f"pre-trace: {t*1e3:.1f} ms/step "
          f"({batch/t:.1f} img/s)", file=sys.stderr)

    logdir = os.path.abspath("prof_resnet")
    jax.profiler.start_trace(logdir)
    for _ in range(5):
        state, opt_state, loss = step(state, opt_state, sb)
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()

    # Aggregate device-lane op durations from the trace proto's JSON
    # export (trace.json.gz under plugins/profile/<run>/).
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")),
        key=os.path.getmtime)
    if not paths:
        print("no trace.json.gz produced", file=sys.stderr)
        return
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # Device lanes: pids whose process_name mentions TPU/device; fall
    # back to "all complete events with args.long_name" (XLA ops).
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "args" in e}
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device:" in n or "XLA" in n.upper()}
    agg = {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        if dev_pids and e.get("pid") not in dev_pids:
            continue
        dur = e.get("dur", 0) / 1e3  # ms
        name = e.get("name", "?")
        # Bucket by op prefix (fusion kind / HLO category).
        key = name.split(".")[0].split("(")[0][:60]
        agg[key] = agg.get(key, 0.0) + dur
        total += dur
    print(f"device trace: {len(events)} events, "
          f"{total:.1f} ms total over 5 steps", file=sys.stderr)
    for k, v_ in sorted(agg.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{v_ / 5:9.3f} ms/step  {100 * v_ / max(total, 1e-9):5.1f}%  "
              f"{k}", file=sys.stderr)


if __name__ == "__main__":
    main()
