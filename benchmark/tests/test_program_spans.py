"""The readers of the program's own spans (`hvd.*`): `span_idle` and
`span_stat` on hand-made busy intervals and spans, `program_spans` on a
trace made here on the CPU, and both readers with nothing to read.  (No
trace from the chip beside probe.xplane.pb: the smallest serving one, a
one-layer server of two rows over two steps, is 617 KB.)"""
import glob
import importlib
import inspect
import json
import os
import shutil
import types

import pytest

from benchmark.readers import ReadContext, span_idle, span_stat
from benchmark.reduce import program_spans, xplane
from benchmark.reduce.program_spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def ctx_of(reduced, cell="a_cell"):
    return ReadContext(cell={"name": cell}, config={}, traffic={}, peaks={},
                       chips=1, counters={}, samples={}, trace=reduced,
                       memory_peak_bytes=0)


def reduced_of(busy, lo, hi):
    chip = xplane.ChipTrace([("op", s, e) for s, e in busy], [])
    return xplane.Reduced([chip], [], lo, hi)


# A window of 10 s.  One step from 1 to 9, cut into three phases at 4 and
# 6; the device is busy from 0 to 2, 3.5 to 4.5 and 7 to 10.
BUSY = [(0.0, 2.0), (3.5, 4.5), (7.0, 10.0)]
STEP = [Span("hvd.serve.step", 1.0, 9.0, {"step": 3})]
PARTS = [Span("hvd.serve.admit", 1.0, 4.0, {}),
         Span("hvd.serve.launch", 4.0, 6.0, {}),
         Span("hvd.serve.fetch", 6.0, 9.0, {})]


def test_intersect_by_hand():
    assert span_idle.intersect([(0, 2), (3, 5), (6, 7)],
                               [(1, 4), (4.5, 6.5)]) == \
        [(1, 2), (3, 4), (4.5, 5), (6, 6.5)]
    assert span_idle.intersect([(0, 1)], []) == []
    assert span_idle.intersect([(0, 1)], [(1, 2)]) == []


def test_phases_that_partition_a_step_sum_to_its_idle():
    def idle(spans):
        return span_idle.idle_inside(
            BUSY, [(s.start_s, s.end_s) for s in spans], 0.0, 10.0)
    # idle: 2-3.5 and 4.5-7, both inside the step
    assert idle(STEP) == pytest.approx(4.0)
    parts = [idle([p]) for p in PARTS]
    # the gap 4.5-7 straddles launch and fetch: 1.5 s to one, 1 s to the
    # other, not all of it to the phase that holds its middle
    assert parts == pytest.approx([1.5, 1.5, 1.0])
    assert sum(parts) == pytest.approx(idle(STEP))


def test_idle_is_clipped_to_the_window():
    # the window opens inside the step and closes inside a busy stretch
    assert span_idle.idle_inside(BUSY, [(1.0, 9.0)], 3.0, 8.0) == \
        pytest.approx(0.5 + 2.5)


def test_span_idle_read(monkeypatch):
    monkeypatch.setattr(program_spans, "of_cell",
                        lambda cell: tuple(STEP + PARTS))
    ctx = ctx_of(reduced_of(BUSY, 0.0, 10.0))
    assert span_idle.read(ctx, "hvd.serve.step") == pytest.approx(40.0)
    assert span_idle.read(ctx, "hvd.serve.launch") == pytest.approx(15.0)
    assert span_idle.read(ctx, "hvd.serve.sample") is None   # never entered


def steps_and_prefills():
    spans = []
    for i in range(4):                       # steps of 10, 10, 30, 10 ms
        start = 1.0 + 0.1 * i
        spans.append(Span("hvd.serve.step", start,
                          start + (0.03 if i == 2 else 0.01), {"step": i}))
    spans.append(Span("hvd.serve.prefill", 1.205, 1.225,
                      {"req": 9, "queue_wait_us": 1500.0}))
    spans.append(Span("hvd.serve.prefill", 1.226, 1.228,
                      {"req": 10, "queue_wait_us": 2500.0}))
    # one more step, cut by the window's end
    spans.append(Span("hvd.serve.step", 1.95, 2.05, {"step": 4}))
    return sorted(spans, key=lambda s: s.start_s)


def test_span_stat_without_and_arg():
    spans = steps_and_prefills()
    every = span_stat.values(spans, 0.0, 2.0, "hvd.serve.step")
    assert every == pytest.approx([10.0, 10.0, 30.0, 10.0])
    decode_only = span_stat.values(spans, 0.0, 2.0, "hvd.serve.step",
                                   without="hvd.serve.prefill")
    assert decode_only == pytest.approx([10.0, 10.0, 10.0])
    waits = span_stat.values(spans, 0.0, 2.0, "hvd.serve.prefill",
                             arg="queue_wait_us", scale=1e-3)
    assert waits == pytest.approx([1.5, 2.5])
    assert span_stat.values(spans, 0.0, 2.0, "hvd.serve.prefill",
                            arg="no_such_argument") == []


def test_span_stat_read(monkeypatch):
    monkeypatch.setattr(program_spans, "of_cell",
                        lambda cell: tuple(steps_and_prefills()))
    ctx = ctx_of(reduced_of([(0.0, 2.0)], 0.0, 2.0))
    assert span_stat.read(ctx, "hvd.serve.step", "mean_ms") == \
        pytest.approx(15.0)
    assert span_stat.read(ctx, "hvd.serve.step", "mean_ms",
                          without="hvd.serve.prefill") == pytest.approx(10.0)
    assert span_stat.read(ctx, "hvd.serve.prefill", "p50") == \
        pytest.approx(11.0)
    assert span_stat.read(ctx, "hvd.serve.prefill", "p50",
                          arg="queue_wait_us", scale=1e-3) == \
        pytest.approx(2.0)
    assert span_stat.read(ctx, "hvd.serve.fetch", "mean_ms") is None
    with pytest.raises(ValueError):
        span_stat.read(ctx, "hvd.serve.step", "median")


def test_readers_return_none_with_no_trace(tmp_path):
    # an untraced run has no reduced trace; a traced run of a program
    # without spans (the parent, a training cell) has no hvd.* event
    assert program_spans.of_cell("a_cell", checkout=str(tmp_path)) == ()
    for ctx in (ctx_of(None), ctx_of(reduced_of(BUSY, 0.0, 10.0),
                                     cell="no_such_cell_was_traced")):
        assert span_idle.read(ctx, "hvd.serve.fetch") is None
        assert span_stat.read(ctx, "hvd.serve.step", "mean_ms") is None


def test_program_spans_on_a_trace_made_here(tmp_path):
    """What `horovod_tpu.utils.timeline.span` writes is what this module
    reads, found where the harness leaves a cell's trace."""
    import jax
    from horovod_tpu.utils.timeline import span

    trace_dir = tmp_path / ".bench_trace" / "a_cell"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.server.step"):
        with span("step", "serve", {"step": 5, "queued": 2, "active": 1}):
            with span("admit", "serve"):
                pass
            with span("observe", "serve", {"step": 5, "rows": 1}):
                pass
    jax.profiler.stop_trace()
    spans = program_spans.of_cell("a_cell", checkout=str(tmp_path))
    assert [s.name for s in spans] == [
        "hvd.serve.step", "hvd.serve.admit", "hvd.serve.observe"]
    step, admit, observe = spans
    assert step.stats == {"step": 5, "queued": 2, "active": 1}
    assert admit.stats == {} and observe.stats == {"step": 5, "rows": 1}
    assert step.start_s <= admit.start_s <= admit.end_s <= observe.start_s \
        <= observe.end_s <= step.end_s
    assert program_spans.named(spans, "hvd.serve.admit", step.start_s,
                               step.end_s) == [admit]
    assert program_spans.named(spans, "hvd.serve.admit", admit.end_s,
                               step.end_s) == []
    # a second run of the cell leaves a newer file, and that one is read
    shutil.rmtree(trace_dir)
    jax.profiler.start_trace(str(trace_dir))
    with span("fetch", "serve"):
        pass
    jax.profiler.stop_trace()
    assert [s.name for s in program_spans.of_cell(
        "a_cell", checkout=str(tmp_path))] == ["hvd.serve.fetch"]


def test_every_metric_file_fits_its_reader():
    """Each per-layer entry of BENCHMARK.json has a metric file whose
    reader exists and takes the file's parameters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files = {os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "metrics", "*.json"))}
    assert files == {m["name"] for m in manifest["per_layer"]}
    for name in sorted(files):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        inspect.signature(reader.read).bind(
            types.SimpleNamespace(), **spec.get("params", {}))
