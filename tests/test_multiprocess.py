"""REAL cross-process collective tests: two OS processes bootstrap
`jax.distributed` (CPU backend, gloo cross-process collectives) through
the launcher and move actual tensors between processes.

Reference parity: SURVEY.md §4 — the bulk of Horovod's test suite runs
under a real 2-process `horovodrun`; this file is that pattern, end to
end through `horovodrun_tpu`'s exec path (rendezvous server, env
injection, coordinator bootstrap, collectives, teardown).
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO_ROOT, "tests", "data", "multiproc_main.py")


def _launch(np_, out_dir, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["HVD_TEST_OUT"] = str(out_dir)
    env["JAX_PLATFORMS"] = "cpu"
    # Workers must see exactly one local CPU device each so the global
    # mesh is one-device-per-process.
    env.pop("XLA_FLAGS", None)
    # The consistency checker must be TRANSPARENT for correct programs —
    # including ragged allgather and concurrent disjoint process sets.
    env["HOROVOD_COLLECTIVE_CONSISTENCY_CHECK"] = "1"
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         "python", WORKER],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO_ROOT)


@pytest.mark.integration
class TestCrossProcessCollectives:
    def test_two_process_allreduce(self, tmp_path):
        r = _launch(2, tmp_path)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        results = {}
        for rank in (0, 1):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), \
                f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
            results[rank] = json.loads(path.read_text())
        for rank, res in results.items():
            assert res["size"] == 2
            # sum over ranks: [1,2]*1 + [1,2]*2 = [3,6]
            assert res["allreduce_sum"] == [3.0, 6.0]
            # avg of rank values 0,1 = 0.5
            assert res["allreduce_avg"] == [0.5, 0.5, 0.5]
            # root 0's value
            assert res["broadcast"] == [100.0]
            # concat in rank order
            assert res["allgather"] == [[0.0, 0.0], [1.0, 1.0]]
            # ragged: rank 0 one row, rank 1 two rows
            assert res["allgather_ragged"] == [0.0, 1.0, 1.0]
            # rank r's received chunk from sender s = s
            assert res["alltoall"] == [0.0, 1.0]
            # summed tensor rows, one per rank
            assert res["reducescatter"] == [3.0, 3.0]
            # each rank fed its own rows: mean of rank values 0,1
            assert res["data_parallel_mean"] == 0.5
        # Singleton process sets at np=2: each rank reduces alone.
        assert results[0]["ps_sum"] == [1.0]
        assert results[1]["ps_sum"] == [2.0]
        # Checkpoint: rank 0 wrote; both ranks restored rank 0's state.
        for rank in (0, 1):
            assert results[rank]["ckpt"] == [1.0, 1.0, 1.0]
            assert results[rank]["ckpt_latest"] == 1

    @pytest.mark.slow
    def test_four_process_collectives(self, tmp_path):
        """np=4 (reference floor is 2 processes; SURVEY §4 says go
        beyond): mesh order, every collective, and process-set subsets
        that span non-adjacent processes."""
        self._run_n_process(4, tmp_path, timeout=420)

    @pytest.mark.slow
    def test_eight_process_collectives(self, tmp_path):
        """np=8: contiguous-rank/mesh-order assumptions at the size the
        virtual-device tests simulate, with real processes."""
        self._run_n_process(8, tmp_path, timeout=560)

    def _run_n_process(self, n, tmp_path, timeout):
        r = _launch(n, tmp_path, timeout=timeout)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        results = {}
        for rank in range(n):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), \
                f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
            results[rank] = json.loads(path.read_text())
        total = sum(range(1, n + 1))  # sum of each rank's (rank+1)
        for rank, res in results.items():
            assert res["size"] == n
            assert res["allreduce_sum"] == [1.0 * total, 2.0 * total]
            avg = sum(range(n)) / n
            assert res["allreduce_avg"] == [avg] * 3
            assert res["broadcast"] == [100.0]
            assert res["allgather"] == [[float(s)] * 2 for s in range(n)]
            assert res["allgather_ragged"] == [
                float(s) for s in range(n) for _ in range(s + 1)]
            # mesh/rank order: received chunk s comes from global rank s.
            assert res["alltoall"] == [float(s) for s in range(n)]
            assert res["reducescatter"] == [float(total)] * 2
        # Process sets spanning non-adjacent processes (evens/odds),
        # computed concurrently: each rank sums (r+1) within its set.
        even_sum = float(sum(r + 1 for r in range(0, n, 2)))
        odd_sum = float(sum(r + 1 for r in range(1, n, 2)))
        for rank in range(n):
            expected = even_sum if rank % 2 == 0 else odd_sum
            assert results[rank]["ps_sum"] == [expected], results[rank]


JOIN_WORKER = os.path.join(REPO_ROOT, "tests", "data", "join_main.py")


@pytest.mark.integration
class TestJoinMultiprocess:
    """True join under real multi-process collectives: rank 0 exhausts
    its data first and services rank 1's remaining collectives with zero
    contributions (signature mirroring over the control plane).
    Reference: test_torch.py join cases."""

    def test_uneven_batches_join(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        # Regression: the consistency checker must not deadlock against
        # join mode (it defers to join's own signature protocol).
        env["HOROVOD_COLLECTIVE_CONSISTENCY_CHECK"] = "1"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", JOIN_WORKER],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        res = {}
        for rank in (0, 1):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), f"no result for rank {rank}:\n{r.stdout}"
            res[rank] = json.loads(path.read_text())
        # Rank 0: 3 batches, both ranks active -> avg of (1,2) = 1.5.
        assert res[0]["averages"] == [1.5, 1.5, 1.5]
        # Rank 1: first 3 steps averaged with rank 0 (1.5); after rank 0
        # joins, the average covers rank 1 alone (2.0) — NOT dragged to
        # 1.0 by a zero contribution.
        assert res[1]["averages"] == [1.5, 1.5, 1.5, 2.0, 2.0]
        # Rank 1 joined last.
        assert res[0]["last_joined"] == 1
        assert res[1]["last_joined"] == 1
        # Collectives issued while rank 0 was joined (mirrored with zero
        # contributions — JoinOp covers every enqueue type):
        # reducescatter Average over active count 1 → rank 1's own row.
        assert res[1]["rs"] == [20.0]
        # Fixed alltoall: rank 0 contributes zeros; rank 1 receives
        # [rank0's chunk (0), its own chunk (5)].
        assert res[1]["a2a"] == [0.0, 5.0]
        # Splits alltoall: joined rank sends zero splits — rank 1 receives
        # only its own 2 elements, recv splits [0, 2].
        assert res[1]["a2av"] == [2.0, 3.0]
        assert res[1]["a2av_splits"] == [0, 2]


HIER_WORKER = os.path.join(REPO_ROOT, "tests", "data",
                           "hierarchical_main.py")


@pytest.mark.integration
class TestHierarchicalCrossProcess:
    """Two-tier mesh with the slow tier on a REAL process boundary:
    np=2 processes x 4 virtual devices each fold into the 2x4
    ("dcn", "hvd") hierarchical mesh, so the DCN legs (including the
    int8 wire and the ZeRO-1 reduce-scatter/allgather pair) cross the
    gloo transport instead of staying host-local like the
    single-process suites."""

    def test_two_tier_collectives_cross_process(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        # The worker pins its own 4-device XLA_FLAGS before importing
        # jax; drop the parent's count=8 flag anyway for hygiene.
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", HIER_WORKER],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        for pidx in (0, 1):
            path = tmp_path / f"rank{pidx}.json"
            assert path.exists(), \
                f"process {pidx} wrote no result:\n{r.stdout}\n{r.stderr}"
            res = json.loads(path.read_text())
            assert res["size"] == 8
            # Exact two-level == flat, bit for bit (integer-valued f32).
            assert res["hier_exact_bitwise"], res
            # ZeRO-1 substrate: RS+AG reassembles the exact flat sum.
            assert res["rs_ag_bitwise"], res
            # int8 DCN wire engaged (error nonzero) and bounded.
            assert 0.0 < res["int8_err"] < res["ref_scale"] / 25, res


ZERO_WORKER = os.path.join(REPO_ROOT, "tests", "data", "zero_main.py")


@pytest.mark.integration
class TestZeroCrossProcess:
    """ZeRO-2 and ZeRO-3 end-to-end across a REAL process boundary:
    np=2 gloo workers run two accumulation windows per stage, so every
    per-pass reduce-scatter, just-in-time param gather, and update
    allgather crosses the transport.  The contract under test is the
    ladder's replica consistency: final params bitwise-identical across
    ranks for every stage, stage 2 bitwise-equal to stage 1 +
    early_reduction (integer f32 grads, power-of-two world size), and
    the int8 gather-wire stage-3 variant still rank-identical with
    bounded wire error."""

    def test_zero2_zero3_end_to_end(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        # One CPU device per process: the shard exchange must cross gloo.
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", ZERO_WORKER],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        res = {}
        for rank in (0, 1):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), \
                f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
            res[rank] = json.loads(path.read_text())
        # Replica consistency: every stage's finals bitwise-identical
        # across the process boundary (JSON round-trips f32 exactly).
        for key in ("z1", "z2", "z3", "z3_int8"):
            assert res[0][key] == res[1][key], key
        for rank in (0, 1):
            out = res[rank]
            assert out["z2_bitwise_z1"], out
            assert out["z3_bitwise_z1"], out
            # int8 gather wire engaged: error nonzero but bounded.
            assert 0.0 < out["z3q_maxerr"] < out["z1_scale"] / 10, out
            # Stage-3 residency: ~1/2 of the replicated param bytes.
            assert out["param_resident_bytes"] <= \
                out["param_full_bytes"] // 2 + 8
        # Sanity: training moved the params.
        def _flat(x):
            if isinstance(x, list):
                for v in x:
                    yield from _flat(v)
            else:
                yield x
        assert any(v != 0.0 for leaf in res[0]["z1"]
                   for v in _flat(leaf))


STALL_WORKER = os.path.join(REPO_ROOT, "tests", "data", "stall_main.py")


@pytest.mark.integration
class TestStallInspectorNamesRanks:
    """Reference: stall_inspector.cc reports which ranks have NOT
    submitted a stalled tensor.  Rank 0 lags 8s before the second
    collective; rank 1's inspector (warn=2s) must warn AND name rank 0
    via the control-plane heartbeats; the job then completes normally."""

    def test_lagging_rank_is_named(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "2"
        env["STALL_TEST_SLEEP"] = "8"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", STALL_WORKER],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO_ROOT)
        out = r.stdout + r.stderr
        assert r.returncode == 0, f"launch failed:\n{out}"
        assert "rank 0 done" in out and "rank 1 done" in out
        assert "stalled" in out, out
        assert "Ranks behind: rank 0" in out, out


TRACE_WORKER = os.path.join(REPO_ROOT, "tests", "data",
                            "trace_timeline_main.py")


@pytest.mark.integration
class TestFleetTracerCrossProcess:
    """End-to-end fleet tracer (docs/TRACE.md): two real ranks write
    cycle-marked timelines; `python -m horovod_tpu.trace merge` joins
    them into one Perfetto trace with cross-rank flow events and
    `analyze` attributes the steps."""

    def test_merge_and_analyze_real_rank_timelines(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["HOROVOD_TIMELINE"] = str(tmp_path / "tl.json")
        env["HOROVOD_TIMELINE_ALL_RANKS"] = "1"
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", TRACE_WORKER],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        for rank in (0, 1):
            res = json.loads((tmp_path / f"rank{rank}.json").read_text())
            assert res["cycles"] == 3
            assert res["sums"] == [1.5, 1.5, 1.5]  # avg(1, 2) each step
        rank_files = [str(tmp_path / "tl.json"),
                      str(tmp_path / "tl.rank1.json")]
        for p in rank_files:
            assert os.path.exists(p), f"missing rank timeline {p}"

        # Merge through the real CLI.
        merged_path = tmp_path / "fleet_trace.json"
        m = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.trace", "merge",
             *rank_files, "-o", str(merged_path)],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO_ROOT)
        assert m.returncode == 0, f"merge failed:\n{m.stdout}\n{m.stderr}"
        doc = json.loads(merged_path.read_text())
        events = doc["traceEvents"]
        assert doc["metadata"]["ranks"] == [0, 1]
        assert {e["pid"] for e in events} == {0, 1}
        # The three CYCLE_n barriers each link the two ranks.
        starts = [e for e in events if e["ph"] == "s"]
        finishes = [e for e in events if e["ph"] == "f"]
        assert len(starts) >= 3 and len(starts) == len(finishes)
        assert doc["metadata"]["flow_events"] == len(starts) * 2
        cycle_names = {e["name"] for e in events if e["ph"] == "i"}
        assert {"CYCLE_1", "CYCLE_2", "CYCLE_3"} <= cycle_names

        # Analyze through the real CLI.
        a = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.trace", "analyze",
             *rank_files],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO_ROOT)
        assert a.returncode == 0, f"analyze failed:\n{a.stdout}\n{a.stderr}"
        report = json.loads(a.stdout)
        assert report["summary"]["ranks"] == [0, 1]
        assert report["summary"]["steps_analyzed"] == 3
        assert all(s["skew_ms"] >= 0 for s in report["steps"])
        # The eager allreduces appear as attributed collective buckets.
        assert any(s["buckets"] for s in report["steps"]), report


FLEET_WORKER = os.path.join(REPO_ROOT, "tests", "data",
                            "fleet_metrics_main.py")


@pytest.mark.integration
class TestMetricsFleetViewCrossProcess:
    """Metrics fleet view under real processes (docs/METRICS.md): each
    worker binds an ephemeral scrape endpoint (HOROVOD_METRICS_PORT=0),
    publishes its snapshot to the rendezvous KV, and merges BOTH ranks'
    snapshots into the rendered cluster view."""

    def test_kv_merge_and_ephemeral_exposition(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["HOROVOD_METRICS_PORT"] = "0"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", FLEET_WORKER],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        results = {}
        for rank in (0, 1):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), \
                f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
            results[rank] = json.loads(path.read_text())
        # Ephemeral ports bound and distinct; scrape served Prometheus.
        assert results[0]["port"] != results[1]["port"]
        for rank, res in results.items():
            assert res["port"] > 0
            assert res["scrape_has_calls"] and res["scrape_has_help"]
            # KV fleet merge saw BOTH ranks' snapshots.
            assert sorted(res["fleet_ranks"]) == [0, 1]
            # Counters summed across ranks: each rank did >= 1 collective.
            assert res["calls_total"] >= 2
            # Gauges stay per-rank in the merge.
            assert res["cp_by_rank"] == {"0": 1.5, "1": 2.5}
            assert res["render"].startswith("fleet view: 2 rank(s)")
            assert "step critical path (ms): rank0=1.5  rank1=2.5" in (
                res["render"])


CC_WORKER = os.path.join(REPO_ROOT, "tests", "data", "consistency_main.py")


@pytest.mark.integration
class TestCollectiveConsistencyCheck:
    """Semantic race detection (reference: controller.cc duplicate-name
    / mismatched-shape errors): under the debug flag, divergent
    collectives fail fast with a per-rank signature dump instead of
    hanging the compiled collective."""

    def _launch(self, mode):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["HOROVOD_COLLECTIVE_CONSISTENCY_CHECK"] = "1"
        env["CC_TEST_MODE"] = mode
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", CC_WORKER],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=REPO_ROOT)

    def test_matching_collectives_pass(self):
        r = self._launch("match")
        out = r.stdout + r.stderr
        assert r.returncode == 0, out
        assert "rank 0 done" in out and "rank 1 done" in out

    def test_mismatched_shape_fails_fast_with_dump(self):
        r = self._launch("mismatch")
        out = r.stdout + r.stderr
        assert r.returncode != 0
        assert "consistency check FAILED" in out, out
        assert "process 0:" in out and "process 1:" in out, out


RESHARD_WORKER = os.path.join(REPO_ROOT, "tests", "data",
                              "reshard_main.py")


@pytest.mark.integration
class TestReshardCrossProcess:
    """Live resharding across a REAL process boundary (docs/RESHARD.md):
    np=2 gloo workers build genuine ZeRO-3 state (mid-window stage-2
    accumulation, adam rows, generation-stamped EF residuals), then
    shrink 2→1 and grow 1→2 through the peak-bounded chunk mover.  The
    contract: the live redistribution is BITWISE-identical to the legacy
    checkpoint-restore-then-restack path, the measured staging peak
    stays under the configured ceiling, and an injected `reshard.peer_die`
    mid-publish degrades every rank to the old restore path with the
    guard digest verifying the restored state."""

    def test_shrink_grow_and_peer_death(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HVD_TEST_OUT"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "python", RESHARD_WORKER],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO_ROOT)
        assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
        res = {}
        for rank in (0, 1):
            path = tmp_path / f"rank{rank}.json"
            assert path.exists(), \
                f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
            res[rank] = json.loads(path.read_text())
        # Shrink: live == local restack == from-checkpoint restore,
        # peak ASSERTED under the ceiling, chunking actually engaged.
        assert res[0]["shrink_live_eq_local"], res[0]
        assert res[0]["shrink_live_eq_restore"], res[0]
        for rank in (0, 1):
            out = res[rank]
            assert out["shrink_peak_ok"], out
            assert 0 < out["shrink_peak"] <= out["peak_ceiling"], out
            assert out["shrink_multichunk"], out
        # Grow: compat restack == local fold, rows round-trip bitwise,
        # and the cross-replica guard digest agrees.
        for rank in (0, 1):
            out = res[rank]
            assert out["grow_bitwise"], out
            assert out["grow_rows_roundtrip"], out
            assert out["grow_digest_mismatch"] is None, out
            # The elastic state API end to end (same-N reshard is
            # identity, scalars broadcast, step survives).
            assert out["class_rows_bitwise"], out
            assert out["class_state_bitwise"], out
            assert out["class_step"] == 7, out
            # Peer death: every rank degrades, then the legacy restore
            # path reproduces the pre-reshard state bitwise.
            assert out["die_degraded"], out
            assert out["die_restore_bitwise"], out
            assert out["die_restore_digest_mismatch"] is None, out
        assert res[1]["die_points_hit"] == 1, res[1]
        assert res[0]["die_points_hit"] == 0, res[0]


CHAOS_WORKER = os.path.join(REPO_ROOT, "tests", "data", "chaos_main.py")


def _launch_chaos(np_, out_dir, generations, steps_per_gen,
                  extra_env=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["HVD_TEST_OUT"] = str(out_dir)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update({
        # Per-rank cycle-marked timelines feed the online windows; the
        # Python writer keeps partial files readable mid-run.
        "HOROVOD_TIMELINE": str(out_dir / "tl.json"),
        "HOROVOD_TIMELINE_ALL_RANKS": "1",
        "HOROVOD_TIMELINE_MARK_CYCLES": "1",
        "HOROVOD_TIMELINE_DISABLE_NATIVE": "1",
        # Online autotuner against the merged-trace objective.
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        # Reaction policy tight enough to fire inside the soak.
        "HOROVOD_STRAGGLER_PATIENCE": "2",
        "HOROVOD_STRAGGLER_COOLDOWN": "1",
        "HOROVOD_CHAOS_GENERATIONS": str(generations),
        "HOROVOD_CHAOS_STEPS_PER_GEN": str(steps_per_gen),
        "HVD_CHAOS_SEED": "7",
    })
    env.update(extra_env or {})
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         "python", CHAOS_WORKER],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO_ROOT)
    assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
    res = {}
    for rank in range(np_):
        path = out_dir / f"rank{rank}.json"
        assert path.exists(), \
            f"rank {rank} wrote no result:\n{r.stdout}\n{r.stderr}"
        res[rank] = json.loads(path.read_text())
    return res


def _assert_soak_invariants(res, np_):
    """The re-convergence contract every soak run must satisfy."""
    for rank, out in res.items():
        assert not out["split_brain"], out
        assert out["final_digest_mismatch"] is None, out
        for ev in out["events"]:
            assert ev["outcome"] in ("recovered", "degraded"), ev
            assert ev["mttr_ms"] >= 0, ev
    # Final params bitwise-identical across every surviving rank.
    for rank in range(1, np_):
        assert res[rank]["final_w"] == res[0]["final_w"], \
            f"rank {rank} params diverged from rank 0"
    # All ranks observed the identical event stream (lockstep plan).
    for rank in range(1, np_):
        assert ([ (e["kind"], e["gen"], e["step"]) for e in
                  res[rank]["events"] ]
                == [ (e["kind"], e["gen"], e["step"]) for e in
                     res[0]["events"] ])
    # Online autotuner: samples flowing, best-observed objective
    # (best-so-far items/sec) non-worsening across windows.
    out0 = res[0]
    assert out0["autotune_enabled"]
    bests = [w["autotune_best"] for w in out0["windows"]
             if w["autotune_best"] is not None]
    assert bests, "autotuner never recorded a window sample"
    assert all(b2 >= b1 for b1, b2 in zip(bests, bests[1:])), bests
    samples = [w["autotune_samples"] for w in out0["windows"]]
    assert samples[-1] >= 1 and samples == sorted(samples), samples


@pytest.mark.integration
class TestChaosSoakFast:
    """np=2 tier-1 chaos soak (docs/CHAOS.md): the orchestrator itself —
    straggler block with a live reaction, one-shot guard/collective
    injections, per-generation merged-trace windows feeding the online
    autotuner — small enough for tier-1."""

    # This soak runs beside five other workers' tests (tier 1 is `-n 6`),
    # and three of its assertions read the wall clock: who the merged
    # trace blames, whether the stall stands out of the step times, and
    # that no clean step does.  At the library's defaults (20 ms a
    # delayed bucket, a 250 ms stall, z > 4) the stall scored 7.4 to 8.6
    # on a busy machine and, in one whole run of three, under 4.  So the
    # injected delays get a margin over the machine's noise, the bar a
    # clean step must clear goes up with them, and what is asserted is
    # the injected fault by name.  Its own time limit: ten times what it
    # takes (12 s alone), not the launcher's 420 s.
    NOISE_MARGIN = {"HVD_CHAOS_STALL_MS": "1500",
                    "HOROVOD_ANOMALY_Z": "6",
                    "HVD_CHAOS_STRAGGLER_DELAY_MS": "40",
                    "HOROVOD_STRAGGLER_SKEW_THRESHOLD": "0.95"}

    def test_two_process_soak(self, tmp_path):
        res = _launch_chaos(2, tmp_path, generations=5, steps_per_gen=4,
                            extra_env=self.NOISE_MARGIN, timeout=150)
        _assert_soak_invariants(res, 2)
        out = res[0]
        # The straggler block armed and the blame stream fired a
        # reaction (patience 2 inside a 4-generation block).
        assert out["straggler_target"] >= 0
        assert any(r["action"] == "rebalance" for r in out["reactions"]), \
            out["reactions"]
        blamed = [w["straggler_rank"] for w in out["windows"]
                  if w["straggler_armed"]]
        assert out["straggler_target"] in blamed, out["windows"]
        # The rebalance repartition went through the LOUD re-init path.
        assert out["loud_reinits"] >= 1, out
        # Both one-shot injections of the event generation recovered.
        kinds = {e["kind"]: e for e in out["events"]}
        assert "worker_stall" in kinds and "nan_grad" in kinds, kinds
        assert kinds["nan_grad"]["outcome"] == "recovered", kinds
        assert kinds["nan_grad"]["steps_lost"] >= 1, kinds
        # Reactions were computed in lockstep on every rank.
        assert res[1]["reactions"] == out["reactions"]
        # Anomaly detectors (docs/TELEMETRY.md): the injected faults
        # are ground truth — the stall (the one whose size this test
        # sets) must be flagged by the step-time monitor, every trip
        # must attribute to an injection (zero false positives on
        # clean steps), and trips name the offending series.
        anom = out["anomaly"]
        assert anom["false_positives"] == 0, anom["events"]
        assert "worker_stall" in anom["detected_kinds"], anom
        assert set(anom["detected_kinds"]) <= set(anom["injected_kinds"])
        for ev in anom["events"]:
            assert ev["series"] in ("hvd_critical_path_ms",
                                    "hvd_steps_total"), ev


@pytest.mark.slow
class TestChaosSoakFleet:
    """np=4 fault-loaded soak — ISSUE 15's acceptance run: >= 5 distinct
    injected fault kinds in one run, every event digest-verified
    recovered (or deliberately degraded), per-event MTTR, straggler
    reaction fires and post-reaction skew drops, autotuner online with
    a non-worsening best objective, final params bitwise-identical."""

    def test_four_process_fault_loaded_soak(self, tmp_path):
        res = _launch_chaos(
            4, tmp_path, generations=8, steps_per_gen=5,
            extra_env={"HOROVOD_WIRE_POLICY": "bf16:65536"},
            timeout=540)
        _assert_soak_invariants(res, 4)
        out = res[0]
        # >= 5 distinct fault kinds survived in ONE run.
        assert len(out["kinds_injected"]) >= 5, out["kinds_injected"]
        recovered = {e["kind"] for e in out["events"]
                     if e["outcome"] == "recovered"}
        assert len(recovered) >= 5, out["events"]
        # Straggler reaction fired and the post-reaction merged-trace
        # ABSOLUTE wait per step dropped while the delay stayed armed
        # (skew_share is a ratio of the critical path, so collapsing to
        # one bucket can raise it even as the time lost shrinks —
        # wait_ms_per_step is the efficacy signal, see trace/measure.py).
        assert any(r["action"] == "rebalance" for r in out["reactions"])
        fired_gen = min(r["gen"] for r in out["reactions"])
        pre = [w["wait_ms_per_step"] for w in out["windows"]
               if w["straggler_armed"] and w["gen"] <= fired_gen
               and w["wait_ms_per_step"] is not None]
        post = [w["wait_ms_per_step"] for w in out["windows"]
                if w["straggler_armed"] and w["gen"] > fired_gen
                and w["wait_ms_per_step"] is not None]
        assert pre and post, out["windows"]
        assert min(post) < max(pre), (pre, post)
        assert out["loud_reinits"] >= 1, out
