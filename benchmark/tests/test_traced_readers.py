"""The readers that take a step's WORK from the program's spans inside the
traced window (`traced_roofline`, `span_idle_union`), on hand-made traces
with the benchmark's own configurations and peaks.

The trace of a 40 s window whose last 5 s were recorded: the decode
program ran each step of those 5 s in the time its work needs at a known
share of the roofline, and the reader reads the share that was built in,
whatever the rest of the window did: it takes no counter of the window
(a window's sum over the traced time reads over 100% whenever the traced
steps are lighter than the window's mean: PERF.md 6, PR 48).  Last, the
manifest's guard: every serving roofline of BENCHMARK.json is read this
way."""
import importlib
import json
import os

import pytest

from benchmark.lib.harness import cell_metrics
from benchmark.readers import (ReadContext, span_idle, span_idle_union,
                               traced_roofline)
from benchmark.reduce import program_spans, xplane
from benchmark.reduce.program_spans import Span
from benchmark.tests import test_program_spans as fixture

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
STEP, PREFILL = "jit__lambda(111)", "jit__lambda(222)"
GMM = r"gmm(\.\d+)?"
LO, HI, PERIOD = 35.0, 40.0, 0.05
SHARE = 85.0                       # of the roofline, built into the trace

# kind -> configuration, traffic and the TRACED steps' work
CODEGEN_WORK = dict(rows=6, live_tokens=15000, ring_tokens=3000,
                    experts_hit=160)
CASES = {
    "decode": ("mistral-7b-serve", "chat_steady",
               dict(rows=6, live_tokens=60000)),
    "moe": ("laguna-xs2-serve", "codegen_steady", CODEGEN_WORK),
    "gmm": ("laguna-xs2-serve", "codegen_steady", CODEGEN_WORK),
    "state": ("brumby-14b-serve", "longdoc_steady",
              dict(rows=8, live_tokens=40000)),
}


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _least_s(kind, ctx, work):
    return traced_roofline.KINDS[kind][2](ctx, work)


def _case(kind, with_args=True, with_runs=True, with_ops=True, lag=0):
    """-> (ReadContext, spans).  `lag`: the `observe` of device step n
    lies in iteration n + lag (a server that keeps `lag` steps in
    flight)."""
    config, traffic, work = CASES[kind]
    config, traffic = (_json("configs", config + ".json"),
                       _json("traffic", traffic + ".json"))
    peaks = _json("peaks.json")["TPU v5 lite"]
    B = traffic["server"]["max_batch"]
    ctx = ReadContext(
        cell={"name": "a_cell"}, config=config, traffic=traffic, peaks=peaks,
        chips=1, samples={}, trace=None, memory_peak_bytes=0, counters={})
    busy = _least_s(kind, ctx, work) / (SHARE / 100)   # the unit timed
    spans, modules, ops = [], [], []
    n = int((HI - LO) / PERIOD)
    for i in range(n):
        at, dstep = LO + i * PERIOD, 7000 + i
        launch = dict(view_read_pct=10.0)
        if with_args:
            launch.update(dstep=dstep, rows_pct=100.0 * work["rows"] / B,
                          **{k: v for k, v in work.items()
                             if k in ("rows", "live_tokens", "ring_tokens")})
        spans.append(Span("hvd.serve.launch", at, at + 0.002, launch))
        observe = dict(step=i, rows=work["rows"], admitted=0, finished=0,
                       decided=1)
        if with_args and i >= lag:
            observe.update(dstep=dstep - lag, **{
                k: v for k, v in work.items() if k == "experts_hit"})
        spans.append(Span("hvd.serve.observe", at + PERIOD - 0.002,
                          at + PERIOD - 0.001, observe))
        if kind == "gmm":        # twelve calls inside a run twice as long
            run = (at + 0.002, at + 0.002 + 2 * busy)
            if with_ops:
                ops += [(f"gmm.{j}" if j else "gmm",
                         run[0] + j * busy / 12,
                         run[0] + (j + 1) * busy / 12) for j in range(12)]
            ops.append(("fusion.9", run[0] + busy, run[1]))
        else:
            run = (at + 0.002, at + 0.002 + busy)
            ops.append(("fusion.9", *run))
        assert run[1] < at + PERIOD - 0.002, "the step does not fit"
        if with_runs:
            modules.append((STEP, *run))
    if with_runs:                  # a prefill program, run less often
        modules += [(PREFILL, LO + 0.04 + i, LO + 0.045 + i)
                    for i in range(4)]
    ctx.trace = xplane.Reduced([xplane.ChipTrace(ops, modules)], [], LO, HI)
    return ctx, spans


def _read_new(monkeypatch, kind, ctx, spans):
    monkeypatch.setattr(program_spans, "of_cell", lambda cell: tuple(spans))
    return traced_roofline.read(ctx, "jit__lambda(", kind,
                                **({"op": GMM} if kind == "gmm" else {}))


@pytest.mark.parametrize("kind", sorted(CASES))
def test_traced_work_over_traced_time(monkeypatch, kind):
    ctx, spans = _case(kind)
    assert _read_new(monkeypatch, kind, ctx, spans) == pytest.approx(SHARE)


@pytest.mark.parametrize("kind", sorted(CASES))
def test_spans_without_the_arguments_read_none(monkeypatch, kind):
    """The parent of the PR that put the work on the spans, and a traced
    cell of another family."""
    ctx, spans = _case(kind, with_args=False)
    assert _read_new(monkeypatch, kind, ctx, spans) is None
    ctx, spans = _case(kind)
    other = next(k for k in sorted(CASES)
                 if traced_roofline.KINDS[k][0]
                 != traced_roofline.KINDS[kind][0])
    assert _read_new(monkeypatch, other, ctx, spans) is None
    ctx.trace = None
    assert _read_new(monkeypatch, kind, ctx, spans) is None


@pytest.mark.parametrize("kind", sorted(CASES))
def test_work_without_a_program_run_raises(monkeypatch, kind):
    ctx, spans = _case(kind, with_runs=False)
    with pytest.raises(RuntimeError, match="no run of a program"):
        _read_new(monkeypatch, kind, ctx, spans)


def test_experts_hit_and_no_kernel_of_that_name_raises(monkeypatch):
    ctx, spans = _case("gmm", with_ops=False)
    with pytest.raises(RuntimeError, match="no operation named"):
        _read_new(monkeypatch, "gmm", ctx, spans)


@pytest.mark.parametrize("kind", ["moe", "gmm"])
def test_a_steps_routing_is_paired_by_dstep(monkeypatch, kind):
    """With a step kept in flight the sync of device step n is made an
    iteration later: the `observe` that says `dstep` n is still step n's."""
    ctx, spans = _case(kind, lag=1)
    steps = traced_roofline.traced_steps(spans, LO, HI)
    assert all(st["experts_hit"] == CASES[kind][2]["experts_hit"]
               for d, st in steps.items() if d != max(steps))
    assert "experts_hit" not in steps[max(steps)]   # its sync came later
    assert _read_new(monkeypatch, kind, ctx, spans) == pytest.approx(SHARE)


def test_mean_work_takes_the_steps_that_carry_every_argument():
    steps = {1: {"rows": 2, "experts_hit": 10}, 2: {"rows": 4},
             3: {"rows": 6, "experts_hit": 30}}
    assert traced_roofline.mean_work(steps, ("rows",)) == {"rows": 4.0}
    assert traced_roofline.mean_work(steps, ("rows", "experts_hit")) == \
        {"rows": 4.0, "experts_hit": 20.0}
    assert traced_roofline.mean_work(steps, ("live_tokens",)) is None
    assert traced_roofline.mean_work({}, ("rows",)) is None


# -- the wait around a step: launch and fetch as one ------------------------

@pytest.mark.parametrize("offset", [0.0, 0.4, -0.7, 1.3, -1.9])
def test_step_idle_wait_is_launch_plus_fetch_whatever_the_offset(
        monkeypatch, offset):
    """`test_program_spans.py`'s step, with the host's clock moved
    against the device's: `launch` and `fetch` trade idle between them,
    the two together keep their sum."""
    moved = [Span(s.name, s.start_s + offset, s.end_s + offset, s.stats)
             for s in fixture.STEP + fixture.PARTS]
    monkeypatch.setattr(program_spans, "of_cell", lambda cell: tuple(moved))
    ctx = fixture.ctx_of(fixture.reduced_of(fixture.BUSY, 0.0, 10.0))
    launch = span_idle.read(ctx, "hvd.serve.launch")
    fetch = span_idle.read(ctx, "hvd.serve.fetch")
    wait = span_idle_union.read(
        ctx, ["hvd.serve.launch", "hvd.serve.fetch"])
    assert wait == pytest.approx(launch + fetch)
    if offset == 0.0:
        assert (launch, fetch, wait) == pytest.approx((15.0, 10.0, 25.0))
    assert span_idle_union.read(ctx, ["hvd.serve.sample"]) is None
    assert span_idle_union.read(fixture.ctx_of(None),
                                ["hvd.serve.launch"]) is None


def test_launch_and_fetch_trade_idle_and_their_union_does_not(monkeypatch):
    """A step whose two spans border one busy stretch inside a longer
    idle one: an offset of the host's clock moves idle from `fetch` to
    `launch` and leaves the union's alone."""
    busy = [(0.0, 1.0), (4.0, 6.0), (9.0, 10.0)]
    readings = []
    for offset in (0.0, 0.5):
        spans = [Span("hvd.serve.launch", 2.0 + offset, 4.0 + offset, {}),
                 Span("hvd.serve.fetch", 4.0 + offset, 8.0 + offset, {})]
        monkeypatch.setattr(program_spans, "of_cell",
                            lambda cell, spans=spans: tuple(spans))
        ctx = fixture.ctx_of(fixture.reduced_of(busy, 0.0, 10.0))
        readings.append((
            span_idle.read(ctx, "hvd.serve.launch"),
            span_idle.read(ctx, "hvd.serve.fetch"),
            span_idle_union.read(
                ctx, ["hvd.serve.launch", "hvd.serve.fetch"])))
    assert readings[0] == pytest.approx((20.0, 20.0, 40.0))
    assert readings[1] == pytest.approx((15.0, 25.0, 40.0))


# -- the manifest: what BENCHMARK.json lists, the files hold ----------------

MANIFEST = _json(os.pardir, "BENCHMARK.json")
SERVING = ("tpot_p90_ms", "serve_tokens_per_s")
TRACED = ("traced_roofline", "traced_latent")


def _serving_roofline(entry):
    return "roofline" in entry["name"] and entry["moves"] in SERVING


@pytest.mark.parametrize("entry", MANIFEST["per_layer"],
                         ids=lambda e: e["name"])
def test_a_listed_metric_has_its_file_and_its_reader(entry):
    """A serving roofline reads work and time in one traced window, or a
    server that keeps a step in flight cannot be measured by it."""
    spec = _json("metrics", entry["name"] + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    assert set(entry.get("workloads", ())) <= {
        w["name"] for w in MANIFEST["workloads"]}
    if _serving_roofline(entry):
        assert spec["reader"] in TRACED, (entry["name"], spec["reader"])


def test_no_metric_file_without_its_entry():
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert files == {m["name"] for m in MANIFEST["per_layer"]}


def _judged_by(cell):
    """The serving end-to-end metrics the cell reports."""
    return {m["name"] for m in cell_metrics(MANIFEST, cell, "end_to_end")
            } & set(SERVING)


@pytest.mark.parametrize(
    "cell", [c for c in MANIFEST["workloads"] if _judged_by(c)],
    ids=lambda c: c["name"])
def test_a_serving_cell_keeps_a_traced_roofline_on_its_metric(cell):
    """... so that a later claim in the cell stays bounded by one."""
    assert any(_serving_roofline(m) and m["moves"] in _judged_by(cell)
               for m in cell_metrics(MANIFEST, cell, "per_layer"))
