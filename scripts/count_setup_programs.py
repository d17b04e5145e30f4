"""The programs a serving cell's set-up asks of the compiler, counted on
the CPU: the cell's own traffic file as it is (grid, ramp, warm pair,
rows, pages of the configuration's `page_tokens`) over the tiny widths of
the benchmark's rehearsals (PERF.md 6, PR 47).  A count, never a time.

    JAX_PLATFORMS=cpu python3 scripts/count_setup_programs.py \\
        laguna_xs2_codegen_steady [checkout]

`checkout` is the tree whose program and benchmark are counted (this one
by default; `git archive <commit> | tar -x -C <dir>` gives another).  What
is compiled follows from shapes' NUMBER and not their size, so the count
is the chip's: the parent of PR 47 reads 154 here for codegen, as every
run of the cell on the chip printed ("set-up: ... 154 programs asked of
the compiler").  One to ten minutes a cell (a tiny model still prefills
the cell's longest prompt).
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import tempfile

CELL = sys.argv[1]
ROOT = os.path.abspath(sys.argv[2] if len(sys.argv) > 2 else os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark.lib import harness  # noqa: E402
from benchmark.tests import tiny  # noqa: E402
from horovod_tpu.serve import pool, server  # noqa: E402


def tiny_widths(name: str, cfg: dict) -> dict:
    """The rehearsal's cut of this configuration's widths; its traffic,
    `page_tokens` and (Mistral's) window stay the cell's."""
    if name == "laguna-xs2-serve":
        from benchmark.tests.test_rehearsal_pattern import TINY_PATTERN
        return dict(TINY_PATTERN, sliding_window=128)
    if name == "gigachat3.1-702b-a36b-serve":
        from benchmark.tests.test_rehearsal_latent import TINY_LATENT
        return dict(TINY_LATENT,
                    assumed=dict(cfg["assumed"], router_bias_std=0.1))
    if name == "mistral-7b-serve":
        return {k: v for k, v in tiny.TINY_LM.items()
                if k != "sliding_window"}
    raise SystemExit(f"no tiny widths known for {name}")


def main() -> None:
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(tmp)
        _, cell, config, _, _ = harness.find_cell(ROOT, CELL)
        for kind, name, cut in (("configs", cell["config"], tiny_widths),
                                ("traffic", cell["traffic"], None)):
            d = harness.load_json(os.path.join(
                ROOT, "benchmark", kind, name + ".json"))
            if cut:
                d.update(cut(name, d))
            with open(os.path.join(root, "bench", kind, name + ".json"),
                      "w") as f:
                json.dump(d, f)
        prefills = []
        make = server._prefill_fn
        server._prefill_fn = lambda cfg: (
            prefills.append(make(cfg)) or prefills[-1])
        counter = harness.CompileCounter(jax)
        _, cell, config, traffic, _ = harness.find_cell(root, CELL)
        runner = importlib.import_module(
            "benchmark.runners." + config["runner"]).Runner(harness.Context(
                root=root, cell=cell, config=config, traffic=traffic,
                seed=3000000019, devices=jax.devices()[:1],
                peaks=tiny.PEAKS))
        held = {id(f): f._cache_size() for f in prefills}
        print(f"{CELL} at {ROOT}: set-up asked {counter.take()} programs "
              f"of the compiler; _prefill_fn holds "
              f"{sorted(held.values())}, _scatter_pages_jit "
              f"{pool._scatter_pages_jit._cache_size()}, _zero_pages_jit "
              f"{pool._zero_pages_jit._cache_size()}, _gather_rows_jit "
              f"{pool._gather_rows_jit._cache_size()}, _scatter_slots_jit "
              f"{pool._scatter_slots_jit._cache_size()}", flush=True)
        runner.window(3.0)
        print(f"{CELL}: a window of 3 s asked {counter.take()} more")


if __name__ == "__main__":
    main()
