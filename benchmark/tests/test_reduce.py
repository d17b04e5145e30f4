"""The trace reduction on a small recorded trace (probe.xplane.pb: a TPU
v5e running two small jitted programs under `bench.step` / `bench.sleep`
spans, 34 KB) and on hand-made intervals."""
import os

import pytest

from benchmark.reduce import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


def test_merge_and_gaps_by_hand():
    busy = xplane.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert xplane.total(busy) == pytest.approx(3.0)
    assert xplane.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                            (4.0, 5.0)]
    assert xplane.clip(busy, 1.5, 3.5) == [(1.5, 2.0), (3.0, 3.5)]


def test_op_name():
    assert xplane.op_name(
        "%fusion.148 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(...)") \
        == "fusion.148"


def test_recorded_trace():
    r = xplane.reduce_file(TRACE, 1)
    # first to last device operation, no bench.trace_window span in it
    assert r.window_s == pytest.approx(0.025251727, rel=1e-6)
    # a `while` and its body overlap: merged, not summed
    assert r.busy_s == pytest.approx(0.00205276, rel=1e-5)
    assert r.busy_s < sum(t for _, t in r.top_ops(100))
    runs = r.module_runs()
    assert {n.split("(")[0]: len(v) for n, v in runs.items()} == {
        "jit_scan_fn": 3, "jit_mm": 2}
    scan = next(v for n, v in runs.items() if n.startswith("jit_scan_fn("))
    assert sum(scan) == pytest.approx(0.00174387, rel=1e-5)
    assert r.top_ops(1)[0][0] == "while"
    gaps = dict(r.top_gaps(10))
    # the device sat idle while the host slept between the steps
    assert gaps["sleep"] == pytest.approx(0.0231028, rel=1e-4)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.exposed_collective_s() == 0.0


def test_no_device_operation_is_an_error(tmp_path):
    with pytest.raises(RuntimeError):
        xplane.reduce_dir(str(tmp_path), 1)
