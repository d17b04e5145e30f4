"""Seconds of one whole-prompt prefill of `gigachat3.1-702b-a36b-serve`
(the expanded form, all five layers, published widths) by the flash
kernel's tiles, on the chip; the readings `models/decode.py`
`_LATENT_FLASH_BLOCKS` was set from (PERF.md 6, PR 42).

    chiprun -- python3 benchmark/records/probe_latent_prefill.py \\
        chiprun_out/probe_latent_prefill.jsonl 8192 128x128 512x512 ...

One line a (length, tiles) pair: the compile's seconds, the best and the
median of five runs, the program's temporaries.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    out, lengths, tiles = argv[0], argv[1], argv[2:]
    import jax
    import jax.numpy as jnp

    from benchmark.lib import harness, weights, weights_latent
    from benchmark.runners import latent_serve
    from horovod_tpu.models import decode

    harness.use_compile_cache(ROOT)
    m = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "gigachat3.1-702b-a36b-serve.json"))
    cfg = latent_serve.transformer_config(m)
    key = weights.seed_key(3)
    params = jax.jit(
        lambda k: weights_latent.params(k, m, jnp.bfloat16))(key)
    with open(out, "a") as f:
        for T in (int(x) for x in lengths.split(",")):
            prompt = weights.lm_tokens(key, 0, 1, T, m["vocab_size"])
            for tile in tiles:
                decode._LATENT_FLASH_BLOCKS = tuple(
                    int(x) for x in tile.split("x"))
                fn = jax.jit(lambda p, c, t: decode.transformer_prefill(
                    p, c, t, cfg), donate_argnums=(1,))
                fresh = lambda: decode.init_decode_cache(cfg, 1, T + 512)
                t0 = time.perf_counter()
                compiled = fn.lower(params, fresh(), prompt).compile()
                compile_s = time.perf_counter() - t0
                runs = []
                for _ in range(6):
                    cache = fresh()
                    jax.block_until_ready(cache)
                    t0 = time.perf_counter()
                    lg, cache = compiled(params, cache, prompt)
                    lg.block_until_ready()
                    runs.append(time.perf_counter() - t0)
                    del cache
                line = {"prompt_tokens": T, "tiles": tile,
                        "compile_s": round(compile_s, 2),
                        "best_s": min(runs[1:]),
                        "median_s": statistics.median(runs[1:]),
                        "temp_bytes": compiled.memory_analysis()
                        .temp_size_in_bytes}
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
