"""What `memory_stats()` counts on the chip, and whether a deeper or wider
`mistral7b_train_4k` runs there (PR 25's review asked for both).

    python3 benchmark/records/memory_probe.py temp
        one program whose only large buffer is a 2 GiB temporary: the
        runtime's counters before and after it, beside XLA's own
        `memory_analysis` of the program
    python3 benchmark/records/memory_probe.py train <layers> <batch>
        the training cell's runner built at that depth and batch: the
        compiler's or the runtime's refusal, or three steps and the
        counters after them

Each prints `PROBE {...}` lines; `memory_probe.jsonl` keeps them.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def say(**kw):
    print("PROBE " + json.dumps(kw), flush=True)


def analysis(compiled):
    a = compiled.memory_analysis()
    return {k: int(getattr(a, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(a, k)}


def temp():
    import jax
    import jax.numpy as jnp
    d = jax.devices()[0]
    say(what="start", stats=d.memory_stats())
    x = jnp.ones((32768, 8192), jnp.bfloat16)      # 512 MiB
    w = jnp.ones((8192, 32768), jnp.bfloat16)      # 512 MiB

    def f(x, w):
        a = jnp.tanh(x @ w)                        # 32768^2 bf16: 2 GiB
        return a @ x + a.T @ x                     # used twice: it is kept

    compiled = jax.jit(f).lower(x, w).compile()
    say(what="program", analysis=analysis(compiled))
    jax.block_until_ready((x, w))
    say(what="arguments resident", stats=d.memory_stats())
    y = compiled(x, w)
    jax.block_until_ready(y)
    say(what="after the program ran", stats=d.memory_stats())


def train(layers: int, batch: int):
    import jax
    from benchmark.lib import harness
    manifest, cell, config, traffic, bench_dir = harness.find_cell(
        ROOT, "mistral7b_train_4k")
    config = dict(config, num_hidden_layers=layers)
    traffic = dict(traffic, per_chip_batch=batch)
    devices = jax.devices()[:1]
    peaks = harness.load_json(os.path.join(bench_dir, "peaks.json"))[
        devices[0].device_kind]
    ctx = harness.Context(root=ROOT, cell=cell, config=config,
                          traffic=traffic, seed=7, devices=devices,
                          peaks=peaks)
    from benchmark.runners import lm_train
    try:
        r = lm_train.Runner(ctx)
        jax.block_until_ready(r.params)
    except Exception as e:   # the refusal is the reading
        text = str(e)
        say(what="train", layers=layers, batch=batch, ran=False,
            error=type(e).__name__, message=text[:1500],
            stats=devices[0].memory_stats())
        return
    out = dict(what="train", layers=layers, batch=batch, ran=True,
               losses=r.program["losses"], stats=devices[0].memory_stats())
    try:
        out["analysis"] = analysis(r.step.lower(
            r.params, r.opt_state, r.batches[0]).compile())
    except Exception as e:
        out["analysis_error"] = str(e)[:300]
    say(**out)


if __name__ == "__main__":
    if sys.argv[1] == "temp":
        temp()
    else:
        train(int(sys.argv[2]), int(sys.argv[3]))
