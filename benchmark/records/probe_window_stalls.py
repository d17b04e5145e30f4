"""A probe, not part of a run (PR 48): `benchmark/run.py` with the garbage
collector and every runner step inside the window timed, and with
DIAG_SKIP_CHECK=1 the reference left out, for runs that ask how widely a
window's numbers spread and why (PERF.md 6, PR 48's second session: the
collector takes 2 to 4 ms of a window; a step far over the longest
prefill is the host standing still).

    python3 benchmark/records/probe_window_stalls.py --workload <cell> \
        --seed <n> --seconds 40 --trace 0
"""
import time

T_START = time.perf_counter()

import gc
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

GC_EVENTS = []          # (start, seconds, generation)
_gc_t = [0.0]


def _gc_cb(phase, info):
    if phase == "start":
        _gc_t[0] = time.perf_counter()
    else:
        GC_EVENTS.append((_gc_t[0], time.perf_counter() - _gc_t[0],
                          info["generation"]))


gc.callbacks.append(_gc_cb)
MARK = {}


class _Shim:
    @staticmethod
    def import_module(name):
        mod = importlib.import_module(name)
        if name.startswith("benchmark.runners.") and not getattr(
                mod, "_diag", False):
            mod._diag = True
            R = mod.Runner
            window, step = R.window, getattr(R, "_step", None)
            steps = []

            def timed_window(self, seconds):
                MARK["t0"] = time.perf_counter()
                steps.clear()
                out = window(self, seconds)
                MARK["t1"] = time.perf_counter()
                MARK["steps"] = list(steps)
                return out

            R.window = timed_window
            if step is not None:
                def timed_step(self, clock):
                    t = time.perf_counter()
                    step(self, clock)
                    steps.append(time.perf_counter() - t)
                R._step = timed_step
            if os.environ.get("DIAG_SKIP_CHECK") == "1":
                R.check = lambda self: []
        return mod


harness.importlib = _Shim


def report():
    if "t0" not in MARK:
        return
    t0, t1 = MARK["t0"], MARK["t1"]
    inside = [(s, d, g) for s, d, g in GC_EVENTS if t0 <= s <= t1]
    by = {g: [d for _, d, gg in inside if gg == g] for g in (0, 1, 2)}
    print("diag gc in the window (%.1f s): " % (t1 - t0) + "; ".join(
        f"gen{g} {len(v)} runs {sum(v) * 1e3:.1f} ms longest "
        f"{max(v, default=0) * 1e3:.2f} ms" for g, v in by.items()),
        file=sys.stderr)
    st = sorted(MARK.get("steps", []))
    if st:
        n = len(st)
        q = lambda p: st[min(n - 1, int(p * n))] * 1e3
        print(f"diag steps in the window and drain: {n}; p50 {q(.5):.3f} "
              f"p90 {q(.9):.3f} p99 {q(.99):.3f} ms; longest five "
              + " ".join(f"{x * 1e3:.1f}" for x in st[-5:])
              + f"; over 100 ms: {sum(1 for x in st if x > .1)} "
              f"({sum(x for x in st if x > .1):.3f} s)", file=sys.stderr)
    print(f"diag gc thresholds {gc.get_threshold()} counts now "
          f"{gc.get_count()} frozen {gc.get_freeze_count()}", file=sys.stderr)


if __name__ == "__main__":
    rc = harness.main(sys.argv[1:], ROOT, T_START)
    report()
    sys.exit(rc)
