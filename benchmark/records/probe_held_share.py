"""How far the share of (token, expert) pairs that lies in the 16 held
experts of `gigachat3.1-702b-a36b-serve` wanders from seed to seed, by
the seeded router bias's spread (`assumed.router_bias_std`), on the chip:
one 4096-token prompt a seed through the program's own prefill, the
layers' `pairs_here` read from the cache's `routed`.  What the file's
`router_bias_std` was set from (PERF.md 6, PR 42): a step reads 88 MB an
expert hit, so the held experts' popularity moves `tpot_p90_ms`.

    chiprun -- python3 benchmark/records/probe_held_share.py \\
        chiprun_out/probe_held_share.jsonl 0.02,0.005,0 11,12,13,14,15,16
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    out, stds, seeds = argv
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness, weights, weights_latent
    from benchmark.runners import latent_serve
    from horovod_tpu.models import decode

    harness.use_compile_cache(ROOT)
    m = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "gigachat3.1-702b-a36b-serve.json"))
    cfg = latent_serve.transformer_config(m)
    T, k = 4096, m["num_experts_per_tok"]
    prefill = jax.jit(lambda p, c, t: decode.transformer_prefill(
        p, c, t, cfg)[1]["routed"])
    with open(out, "a") as f:
        for std in (float(x) for x in stds.split(",")):
            mm = dict(m, assumed=dict(m["assumed"], router_bias_std=std))
            make = jax.jit(
                lambda key: weights_latent.params(key, mm, jnp.bfloat16))
            for seed in (int(x) for x in seeds.split(",")):
                key = weights.seed_key(seed)
                params = make(key)
                routed = np.asarray(prefill(
                    params, decode.init_decode_cache(cfg, 1, T),
                    weights.lm_tokens(key, 0, 1, T, m["vocab_size"])))
                del params
                line = {"router_bias_std": std, "seed": seed,
                        "pairs_here_pct": [
                            round(100.0 * int(n) / (T * k), 3)
                            for n in routed[:, 2]],
                        "experts_hit": routed[:, 0].tolist()}
                line["mean_pct"] = round(
                    float(np.mean(line["pairs_here_pct"])), 3)
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
