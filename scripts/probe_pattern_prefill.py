"""What the flash kernel's tiles cost a served pattern's prompt pass, on
the chip: the readings `models/decode.py` `prompt_tiles` was set from
(PERF.md 6, PR 44; the lines are `benchmark/records/
probe_pattern_prefill.jsonl`).

    chiprun -- python3 scripts/probe_pattern_prefill.py \\
        chiprun_out/probe_pattern_prefill.jsonl kernels prefill

`kernels`: the forward kernel alone at `laguna-xs2-serve`'s two shapes
(48 heads, no window; 64 heads, window 512; 8 KV heads of 128, bf16) at
KERNEL_LENGTHS x TILES and at the tiles `prompt_tiles` picks (640 x 640 for
1100 tokens), a length that a line's tile does not divide padded up to
it as `_flash_prompt` pads; and the one line the uniform models'
question wants (32 heads on 8 at 2048 tokens, the kernel against the
dense path `prompt_attention: auto` takes).  `prefill`: one whole
`transformer_prefill` of the configuration at PREFILL_LENGTHS x
PREFILL_TILES, each forced on the program through `decode.prompt_tiles`
(128 x 128 is the program as it was before PR 44), and at the tiles the
program picks by itself (`rule`).
One line a reading: the seconds the compile took (the script sets no
cache; on a machine that comes with one a program met before is read back
in a second, as the `rule` lines are, each the program of the line before
it), the device's seconds a call (ten calls queued back to back, the wall
clock over them; the best and the median of five such rounds).
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TILES = ((128, 128), (256, 256), (512, 512), (1024, 1024), (1024, 512),
         (512, 1024))
KERNEL_LENGTHS = (512, 1100, 2048, 2944, 6144)
#: (name, heads, window) of the configuration's two kinds of layer
SHAPES = (("full_attention", 48, None), ("sliding_attention", 64, 512))
PREFILL_TILES = ((128, 128), (256, 256), (512, 512), (1024, 1024), None)
PREFILL_LENGTHS = (512, 2048, 6144)
CALLS, ROUNDS = 10, 5


def timed(compiled, args, fresh=None):
    """(best, median) seconds a call of `compiled(*args)`; `fresh` makes
    the arguments a call consumes (donated), outside the clock."""
    import jax
    rounds = []
    for _ in range(ROUNDS + 1):
        extra = [fresh() for _ in range(CALLS)] if fresh else [()] * CALLS
        jax.block_until_ready(extra)
        t0 = time.perf_counter()
        outs = [compiled(*args[:1], *e, *args[1:]) for e in extra]
        jax.block_until_ready(outs)
        rounds.append((time.perf_counter() - t0) / CALLS)
        del outs
    return min(rounds[1:]), statistics.median(rounds[1:])


def cold(fn, *args):
    """(compiled, seconds it took to lower and compile)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, round(time.perf_counter() - t0, 2)


def kernels(emit) -> None:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.decode import prompt_tiles
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel import sequence as seq_mod

    def qkv(T, heads):
        ks = jax.random.split(jax.random.PRNGKey(T + heads), 3)
        return tuple(jax.random.normal(k, (1, T, h, 128), jnp.bfloat16)
                     for k, h in zip(ks, (heads, 8, 8)))

    for name, heads, window in SHAPES:
        for T in KERNEL_LENGTHS:
            seen = set()
            for bq, bk in TILES + (prompt_tiles(T, 128)[1],):
                big = max(bq, bk)
                Tp = -(-T // big) * big
                tile = (min(bq, Tp), min(bk, Tp))
                if (Tp, tile) in seen:
                    continue
                seen.add((Tp, tile))
                q, k, v = qkv(Tp, heads)
                compiled, compile_s = cold(jax.jit(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=True, window=window, blocks=tile)),
                    q, k, v)
                best, median = timed(compiled, (q, k, v))
                emit({"what": "kernel", "layer": name, "heads": heads,
                      "window": window, "prompt_tokens": T, "padded": Tp,
                      "tiles": "%dx%d" % tile, "compile_s": compile_s,
                      "best_s": best, "median_s": median})
    # a uniform model's prompt (mistral-7b-serve's heads) at 2048 tokens
    q, k, v = qkv(2048, 32)
    for what, fn in (
            ("kernel_uniform", lambda q, k, v: flash_attention(
                q, k, v, causal=True, blocks=(1024, 1024))),
            ("dense_uniform", lambda q, k, v: seq_mod.full_attention(
                q, k, v, causal=True))):
        compiled, compile_s = cold(jax.jit(fn), q, k, v)
        best, median = timed(compiled, (q, k, v))
        emit({"what": what, "heads": 32, "window": None,
              "prompt_tokens": 2048, "compile_s": compile_s,
              "best_s": best, "median_s": median})


def prefill(emit) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import harness, weights, weights_pattern
    from benchmark.runners import pattern_serve
    from horovod_tpu.models import decode

    m = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "laguna-xs2-serve.json"))
    cfg = pattern_serve.transformer_config(m)
    key = weights.seed_key(3)
    params = jax.jit(
        lambda k: weights_pattern.params(k, m, jnp.bfloat16))(key)
    rule = decode.prompt_tiles
    for T in PREFILL_LENGTHS:
        prompt = weights.lm_tokens(key, 0, 1, T, m["vocab_size"])
        fresh = lambda: (decode.init_decode_cache(cfg, 1, T + 512),)
        seen = set()
        for tile in PREFILL_TILES:
            if tile is not None:
                tile = tuple(min(b, T) for b in tile)
                if tile in seen:
                    continue
                seen.add(tile)
                # these tiles forced on every prompt pass of the program
                # (128 x 128: the program as it was before PR 44)
                decode.prompt_tiles = lambda T, d_head, tile=tile: (
                    -(-T // max(tile)) * max(tile), tile)
            else:
                decode.prompt_tiles = rule
            fn = jax.jit(lambda p, c, t: decode.transformer_prefill(
                p, c, t, cfg), donate_argnums=(1,))
            compiled, compile_s = cold(fn, params, *fresh(), prompt)
            best, median = timed(compiled, (params, prompt), fresh)
            emit({"what": "prefill", "prompt_tokens": T,
                  "tiles": "%dx%d" % tile if tile else "rule %dx%d"
                  % rule(T, cfg.d_head)[1],
                  "compile_s": compile_s, "best_s": best,
                  "median_s": median,
                  "ms_per_ktoken": round(1e6 * median / T, 3),
                  "temp_bytes": compiled.memory_analysis()
                  .temp_size_in_bytes})
    decode.prompt_tiles = rule


def main(argv) -> int:
    out, parts = argv[0], argv[1:] or ["kernels", "prefill"]
    import jax
    device = jax.devices()[0]
    with open(out, "a") as f:
        def emit(line):
            line["device"] = device.device_kind
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
        for part in parts:
            {"kernels": kernels, "prefill": prefill}[part](emit)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
