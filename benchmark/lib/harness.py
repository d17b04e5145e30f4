"""What every cell shares: finding its files by name, the chip check,
the compile cache, the measured window's book-keeping, the traced run
and the one JSON line at the end.

A cell is run by the runner its configuration names
(`benchmark/runners/<runner>.py`), which builds the system under test
from the program's public entry points.  A runner has

    Runner(ctx)      build the program, its state and its inputs, warm up
    .window(seconds) the measured window -> WindowResult
    .check()         compare what the window's own program produced with
                     the plain reference -> [Check, ...]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

TRACE_SECONDS = 5.0      # a traced run records this much of its window


@dataclasses.dataclass
class Check:
    what: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)   # NaN compares false


@dataclasses.dataclass
class WindowResult:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Programs asked of the compiler (built or fetched from the
    persistent cache) since the last `take()`."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        self._with_cache = False

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self._with_cache = True
            self.n += 1

    def _dur(self, event, secs, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and not self._with_cache):
            self.n += 1

    def take(self) -> int:
        n, self.n = self.n, 0
        return n


class WindowTracer:
    """Records the last `TRACE_SECONDS` of a window: the runner polls it
    at every step with the seconds elapsed, and stops it at the close."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir = trace_dir
        self.start_at = max(0.0, seconds - TRACE_SECONDS)
        self.span = None

    def poll(self, elapsed: float) -> None:
        if self.span is None and elapsed >= self.start_at:
            import jax
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.trace_window")
            self.span.__enter__()

    def stop(self) -> None:
        import jax
        self.poll(float("inf"))
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    root: str
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    devices: List[Any]
    peaks: Dict
    tracer: Optional[WindowTracer] = None
    timeline_path: Optional[str] = None

    def span(self, name: str):
        """A harness span on the profiler's clock (traced runs only)."""
        if self.tracer is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, workload: str):
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    return manifest, cell, config, traffic, bench_dir


def cell_metrics(manifest: Dict, cell: Dict, group: str) -> List[Dict]:
    """The metrics of `group` this cell reports: those that list it, and
    those with no list whose end-to-end metric the cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def use_compile_cache(root: str) -> None:
    """One fixed directory inside the checkout, unless the machine names
    one; the program's own `configure_compile_cache` then sets nothing."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak(devices, out=None) -> int:
    """Peak bytes held on the fullest chip.  On the TPU the runtime counts
    two things apart: `peak_bytes_in_use` is the live buffers (weights,
    optimizer state, caches, batches, results) and leaves out what a
    program needs while it runs; `peak_bytes_reserved` is what the runtime
    sets aside for the loaded programs' temporaries (activations,
    gradients, attention scores: XLA's `temp_size_in_bytes`, to the
    byte in `records/memory_probe.jsonl`).  A step needs both at once,
    and the compiler refuses a program whose sum passes the chip, so the
    peak is their sum.  A backend that reports no reservation gives its
    live peak alone."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        live = int(stats.get("peak_bytes_in_use", 0))
        programs = int(stats.get("peak_bytes_reserved", 0))
        if out is not None and live + programs > peak:
            print(f"device memory, chip {d.id}: live buffers peak {live}, "
                  f"reserved for programs' temporaries {programs}, of "
                  f"{stats.get('bytes_limit')}", file=out)
        peak = max(peak, live + programs)
    return peak


def read_per_layer(bench_dir: str, metrics: List[Dict], rctx) -> Dict:
    out = {}
    for m in metrics:
        spec = load_json(os.path.join(bench_dir, "metrics",
                                      m["name"] + ".json"))
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(rctx, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             peaks: Optional[Dict] = None, timeline: Optional[str] = None,
             out=sys.stdout) -> int:
    manifest, cell, config, traffic, bench_dir = find_cell(root, workload)
    use_compile_cache(root)
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"need {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    if peaks is None:
        table = load_json(os.path.join(bench_dir, "peaks.json"))
        if kind not in table:
            print(f"device kind {kind!r} is not in peaks.json",
                  file=sys.stderr)
            return 3
        peaks = table[kind]
    print(f"device: platform {devices[0].platform} kind {kind!r} "
          f"count {len(devices)}", file=out)

    compiles = CompileCounter(jax)
    trace_dir = os.path.join(root, ".bench_trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(root=root, cell=cell, config=config, traffic=traffic,
                  seed=seed, devices=devices, peaks=peaks,
                  tracer=WindowTracer(trace_dir, seconds) if trace else None,
                  timeline_path=timeline)
    runner_mod = importlib.import_module(
        "benchmark.runners." + config["runner"])
    runner = runner_mod.Runner(ctx)
    setup_programs = compiles.take()

    setup_s = time.perf_counter() - t_start
    result = runner.window(seconds)
    window_compiles = compiles.take()
    peak = memory_peak(devices, out)

    t_check = time.perf_counter()
    checks = runner.check()
    print(f"reference and comparison: {time.perf_counter() - t_check:.2f} s "
          f"(outside the window and outside set-up)", file=out)
    for c in checks:
        print(f"check {c.what}: {c.value:.6g} (limit {c.limit:g}) "
              f"{'ok' if c.ok else 'NOT CORRECT'}", file=out)
    print(f"set-up: {setup_s:.2f} s, {setup_programs} programs asked of "
          f"the compiler; in the window: {window_compiles}", file=out)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in checks) and result.failed == 0,
        "attempted": result.attempted, "failed": result.failed}
    if trace:
        from benchmark.reduce import xplane
        from benchmark.readers import ReadContext
        reduced = xplane.reduce_dir(trace_dir, len(devices))
        result.counters["window_compiles"] = window_compiles
        rctx = ReadContext(cell=cell, config=config, traffic=traffic,
                           peaks=peaks, chips=len(devices),
                           counters=result.counters,
                           samples=result.samples, trace=reduced,
                           memory_peak_bytes=peak)
        line["metrics"] = read_per_layer(
            bench_dir, cell_metrics(manifest, cell, "per_layer"), rctx)
        for name, runs in sorted(reduced.module_runs().items(),
                                 key=lambda kv: -sum(kv[1]))[:8]:
            print(f"trace module {name}: {len(runs)} runs, "
                  f"{sum(runs):.4f} s", file=out)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["breakdown"] = {"device_ops": reduced.top_ops(10),
                             "idle_gaps": reduced.top_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(result.end_to_end, setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell_metrics(manifest, cell, "end_to_end")}
    line["device"] = device
    print(json.dumps(line), file=out)
    out.flush()
    return 0


def main(argv, root: str, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--timeline", default=None,
                    help="write a per-second record of the window here "
                         "(for benchmark/records; the driver never asks)")
    a = ap.parse_args(argv)
    return run_cell(root, a.workload, a.seed, a.seconds, bool(a.trace),
                    t_start, timeline=a.timeline)
