"""Readings for the limits of `correct`, made on the chip.

    python benchmark/tests/control_on_chip.py --workload <cell> \\
        --seeds 11,12,13 [--seconds 8] [--control fp8]

For every seed, in one process: build the cell as a run does, drive a
short window, and print each number `correct` compares (READING program).
With --control also print the same numbers for the plain reference
computed in that lower precision and put in the program's place (READING
control): the step below the bfloat16 the configurations state is
float8_e4m3.  The limits in the configuration files were set from these
lines (PERF.md quotes them); the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", default="")
    a = ap.parse_args(argv)
    _, cell, config, traffic, bench_dir = harness.find_cell(ROOT, a.workload)
    harness.use_compile_cache(ROOT)
    import jax
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("needs the chip", file=sys.stderr)
        return 3
    peaks = harness.load_json(os.path.join(bench_dir, "peaks.json"))[
        devices[0].device_kind]
    runner_mod = importlib.import_module(
        "benchmark.runners." + config["runner"])
    for seed in (int(x) for x in a.seeds.split(",")):
        ctx = harness.Context(root=ROOT, cell=cell, config=config,
                              traffic=traffic, seed=seed, devices=devices,
                              peaks=peaks)
        runner = runner_mod.Runner(ctx)
        runner.window(a.seconds)
        for side, checks in runner.readings(a.control).items():
            print("READING", json.dumps({
                "workload": a.workload, "seed": seed, "side": side,
                "values": {c.what.split(" (")[0]: c.value for c in checks}}),
                flush=True)
        del runner
        gc.collect()      # the server is held in a reference cycle
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
