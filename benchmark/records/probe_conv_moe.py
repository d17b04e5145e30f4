"""Probes of `lfm2_8b_train_8k` on the chip (PR 37).

    python3 benchmark/records/probe_conv_moe.py train <per_chip_batch>
        the cell's runner built at that batch: the compiler's or the
        runtime's refusal, or three steps, the runtime's memory counters
        after them and XLA's `memory_analysis` of the step
    python3 benchmark/records/probe_conv_moe.py stack
        the three grouped products of one sparse layer, forward and
        backward, at the cell's shapes (131072 sorted pairs of which a
        quarter lie in a group, 8 experts of 2048 x 1792): handed the
        layer's OWN 8 experts, and handed EVERY sparse layer's (4 x 8
        groups of which one layer's have rows, as the served layer is
        handed its stack): milliseconds a call, and the bytes the
        backward's gradient of the weights takes

Each prints `PROBE {...}` lines; `probe_conv_moe.jsonl` keeps them.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "lfm2_8b_train_8k"


def say(**kw):
    print("PROBE " + json.dumps(kw), flush=True)


def analysis(compiled):
    a = compiled.memory_analysis()
    return {k: int(getattr(a, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(a, k)}


def train(batch: int):
    import jax
    from benchmark.lib import harness
    _, cell, config, traffic, bench_dir = harness.find_cell(ROOT, CELL)
    harness.use_compile_cache(ROOT)
    traffic = dict(traffic, per_chip_batch=batch)
    devices = jax.devices()[:1]
    peaks = harness.load_json(os.path.join(bench_dir, "peaks.json"))[
        devices[0].device_kind]
    ctx = harness.Context(root=ROOT, cell=cell, config=config,
                          traffic=traffic, seed=7, devices=devices,
                          peaks=peaks)
    from benchmark.runners import conv_moe_train
    try:
        r = conv_moe_train.Runner(ctx)
        jax.block_until_ready(r.params)
    except Exception as e:   # the refusal is the reading
        say(what="train", batch=batch, ran=False, error=type(e).__name__,
            message=str(e)[:1500], stats=devices[0].memory_stats())
        return
    out = dict(what="train", batch=batch, ran=True,
               losses=r.program["losses"], stats=devices[0].memory_stats())
    t = time.perf_counter()
    for _ in range(5):
        loss = r._dispatch()
    jax.block_until_ready(loss)
    out["step_ms"] = 1e3 * (time.perf_counter() - t) / 5
    try:
        out["analysis"] = analysis(r.step.lower(
            r.params, r.opt_state, r.batches[0]).compile())
    except Exception as e:
        out["analysis_error"] = str(e)[:300]
    say(**out)


def stack():
    import jax
    import jax.numpy as jnp
    from benchmark.lib import harness
    from horovod_tpu.models.experts import grouped_product
    _, _, m, traffic, _ = harness.find_cell(ROOT, CELL)
    harness.use_compile_cache(ROOT)
    D, F = m["hidden_size"], m["moe_intermediate_size"]
    held, layers = m["num_experts"], 4
    P = traffic["per_chip_batch"] * traffic["seq_len"] \
        * m["num_experts_per_tok"]
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (P, D), jnp.bfloat16)
    sizes = jnp.full((held,), P // 4 // held, jnp.int32)
    valid = (jnp.arange(P) < P // 4)[:, None]

    def layer(x, w, sizes):
        xs = jnp.where(valid, x, 0)
        up = grouped_product(xs, w["wi"].astype(x.dtype), sizes, x.dtype)
        gate = jax.nn.silu(grouped_product(
            xs, w["wg"].astype(x.dtype), sizes, x.dtype).astype(jnp.float32))
        mid = jnp.where(valid, (up * gate).astype(x.dtype), 0)
        y = grouped_product(mid, w["wd"].astype(x.dtype), sizes, x.dtype)
        return jnp.sum(jnp.where(valid, y, 0).astype(jnp.float32))

    for what, groups, pad in (("own", held, (0, 0)),
                              ("whole", layers * held,
                               (held, (layers - 2) * held))):
        w = {"wi": jax.random.normal(k[1], (groups, D, F), jnp.float32),
             "wg": jax.random.normal(k[2], (groups, D, F), jnp.float32),
             "wd": jax.random.normal(k[3], (groups, F, D), jnp.float32)}
        f = jax.jit(jax.grad(layer, argnums=(0, 1)))
        s = jnp.pad(sizes, pad)
        compiled = f.lower(x, w, s).compile()
        jax.block_until_ready(f(x, w, s))
        t = time.perf_counter()
        for _ in range(5):
            out = f(x, w, s)
        jax.block_until_ready(out)
        say(what="stack", handed=what, groups=groups,
            ms=1e3 * (time.perf_counter() - t) / 5,
            weight_gradient_bytes=3 * groups * D * F * 4,
            analysis=analysis(compiled))
        del w, out


if __name__ == "__main__":
    if sys.argv[1] == "train":
        train(int(sys.argv[2]))
    else:
        stack()
