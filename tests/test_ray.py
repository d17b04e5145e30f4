"""Ray integration tests against an injected fake ray module
(reference: test/single/test_ray.py + test_ray_elastic.py's fake local
cluster — SURVEY §4).  The REAL `horovod_tpu.ray` code paths run:
actor-pool start/run/failure, cluster discovery, and the full elastic
driver with Ray discovery + Ray-actor worker spawn (workers are real
subprocesses; only the ray API is faked).
"""

import os
import sys
import time
import threading

import pytest

import horovod_tpu.ray as hvd_ray
from fake_ray import FakeRay
from horovod_tpu.ray import (
    ElasticRayExecutor,
    RayExecutor,
    RayHostDiscovery,
    RayTransport,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fake_ray(monkeypatch):
    """The fake's actors run in this process, so `set_env` writes the
    ranks into the test process's own environment: put it back, or
    whichever test file this worker runs next reads HOROVOD_LOCAL_RANK=1
    (tests/test_basics.py::test_sizes did, when it shared a worker)."""
    fake = FakeRay()
    monkeypatch.setattr(hvd_ray, "_ray", fake)
    saved = dict(os.environ)
    yield fake
    os.environ.clear()
    os.environ.update(saved)


def fn_const():
    return 42


def fn_read_env():
    return os.environ.get("HOROVOD_RANK")


def fn_boom():
    raise RuntimeError("boom from actor")


class TestRayExecutorActors:
    def test_start_assigns_ranks_and_runs(self, fake_ray):
        ex = RayExecutor(num_workers=3)
        ex.start()
        assert len(fake_ray.actors) == 3
        # Orchestration: each actor received its rank env exactly once,
        # with a shared coordinator address.
        set_envs = [c for c in fake_ray.calls if c[1] == "set_env"]
        assert len(set_envs) == 3
        ranks = sorted(int(c[2][0]["HOROVOD_RANK"]) for c in set_envs)
        assert ranks == [0, 1, 2]
        coords = {c[2][0]["HOROVOD_COORDINATOR_ADDR"] for c in set_envs}
        assert len(coords) == 1
        sizes = {int(c[2][0]["HOROVOD_SIZE"]) for c in set_envs}
        assert sizes == {3}
        assert ex.run(fn_const) == [42, 42, 42]
        ex.shutdown()
        assert all(not a._alive for a in fake_ray.actors)

    def test_failure_propagates(self, fake_ray):
        ex = RayExecutor(num_workers=2)
        ex.start()
        with pytest.raises(RuntimeError, match="boom from actor"):
            ex.run(fn_boom)
        # Pool survives a failed call (reference: actors outlive task
        # exceptions).
        assert ex.run(fn_const) == [42, 42]
        ex.shutdown()

    def test_run_remote_then_get(self, fake_ray):
        ex = RayExecutor(num_workers=2)
        ex.start()
        tokens = ex.run_remote(fn_const)
        assert ex.get(tokens) == [42, 42]
        ex.shutdown()

    def test_not_started_raises(self, fake_ray):
        from horovod_tpu.common.exceptions import HorovodTpuError

        with pytest.raises(HorovodTpuError, match="not started"):
            RayExecutor(num_workers=2).run(fn_const)

    def test_use_gpu_rejected(self, fake_ray):
        from horovod_tpu.common.exceptions import HorovodTpuError

        with pytest.raises(HorovodTpuError, match="use_gpu"):
            RayExecutor(num_workers=1, use_gpu=True)


class TestRayHostDiscovery:
    def test_nodes_to_slots(self, fake_ray):
        fake_ray.set_nodes([
            {"Alive": True, "NodeManagerHostname": "a",
             "Resources": {"CPU": 4}},
            {"Alive": True, "NodeManagerHostname": "b",
             "Resources": {"CPU": 2}},
            {"Alive": False, "NodeManagerHostname": "dead",
             "Resources": {"CPU": 8}},
        ])
        d = RayHostDiscovery(fake_ray)
        assert d.find_available_hosts_and_slots() == {"a": 4, "b": 2}

    def test_cpus_per_slot_and_min(self, fake_ray):
        fake_ray.set_nodes([
            {"Alive": True, "NodeManagerHostname": "a",
             "Resources": {"CPU": 5}},
            {"Alive": True, "NodeManagerHostname": "tiny",
             "Resources": {}},
        ])
        d = RayHostDiscovery(fake_ray, cpus_per_slot=2)
        assert d.find_available_hosts_and_slots() == {"a": 2, "tiny": 1}

    def test_advertised_small_cpu_gets_zero_slots(self, fake_ray):
        # min_slots is a floor for nodes that advertise NO CPU resource
        # at all; a node that advertises a small or fractional CPU count
        # is telling us its true capacity and must NOT be rounded up —
        # 1 // 2 == 0 slots, and get_host_assignments simply skips
        # 0-slot hosts.
        fake_ray.set_nodes([
            {"Alive": True, "NodeManagerHostname": "small",
             "Resources": {"CPU": 1}},
            {"Alive": True, "NodeManagerHostname": "frac",
             "Resources": {"CPU": 0.5}},
            {"Alive": True, "NodeManagerHostname": "bare",
             "Resources": {}},
        ])
        d = RayHostDiscovery(fake_ray, cpus_per_slot=2)
        assert d.find_available_hosts_and_slots() == \
            {"small": 0, "frac": 0, "bare": 1}


def fn_elastic_size():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    # Default elastic mode is single-controller JAX per worker; job
    # membership lives in the env the driver/generation protocol
    # maintains (same convention as tests/data/elastic_main.py).
    return int(os.environ["HOROVOD_SIZE"])


def fn_elastic_epochs():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ObjectState(epoch=0)

    @hvd.elastic.run
    def train(state):
        num_epochs = int(os.environ.get("NUM_EPOCHS", "6"))
        marker = os.environ.get("FAIL_MARKER")
        while state.epoch < num_epochs:
            if marker and os.path.exists(marker):
                with open(marker) as f:
                    if f.read().strip() == os.environ.get(
                            "HOROVOD_HOSTNAME"):
                        sys.exit(1)
            time.sleep(float(os.environ.get("EPOCH_TIME", "0.4")))
            state.epoch += 1
            state.commit()
        return int(os.environ["HOROVOD_SIZE"])

    return train(state)


@pytest.mark.integration
class TestElasticRayNative:
    """The REAL elastic driver loop with Ray discovery + Ray transport:
    workers are genuine subprocesses spawned via the per-host agent
    actor, results return through the rendezvous KV."""

    def _clean(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def test_static_run(self, fake_ray, monkeypatch):
        self._clean(monkeypatch)
        ex = ElasticRayExecutor(min_np=2, cpus_per_slot=1)
        results = ex.run(fn_elastic_size)
        assert results == [2, 2]
        # Workers went through the agent actor, not local fork: the
        # fake recorded spawn calls.
        spawns = [c for c in fake_ray.calls if c[1] == "spawn"]
        assert len(spawns) == 2

    def test_rescale_up_mid_run(self, fake_ray, monkeypatch):
        self._clean(monkeypatch)
        monkeypatch.setenv("NUM_EPOCHS", "8")
        monkeypatch.setenv("EPOCH_TIME", "0.4")
        node = {"Alive": True, "NodeManagerHostname": "127.0.0.1",
                "NodeManagerAddress": "127.0.0.1",
                "Resources": {"CPU": 1}}
        fake_ray.set_nodes([node])

        def grow():
            time.sleep(2.0)
            fake_ray.set_nodes([dict(node, Resources={"CPU": 2})])

        t = threading.Thread(target=grow, daemon=True)
        t.start()
        ex = ElasticRayExecutor(min_np=1, cpus_per_slot=1)
        results = ex.run(fn_elastic_epochs)
        t.join()
        # Both final-generation workers finished at size 2.
        assert sorted(results) == [2, 2]

    def test_worker_failure_blacklists_host(self, fake_ray, monkeypatch,
                                            tmp_path):
        self._clean(monkeypatch)
        monkeypatch.setenv("NUM_EPOCHS", "6")
        monkeypatch.setenv("EPOCH_TIME", "0.4")
        monkeypatch.setenv("HVD_TPU_FAKE_LOCAL_HOSTS", "hostA,hostB")
        marker = tmp_path / "fail_marker"
        fake_ray.set_nodes([
            {"Alive": True, "NodeManagerHostname": h,
             "Resources": {"CPU": 1}}
            for h in ("hostA", "hostB")
        ])

        def fail_b():
            time.sleep(1.5)
            marker.write_text("hostB")

        t = threading.Thread(target=fail_b, daemon=True)
        t.start()
        ex = ElasticRayExecutor(
            min_np=1, cpus_per_slot=1,
            extra_env={"FAIL_MARKER": str(marker)})
        results = ex.run(fn_elastic_epochs)
        t.join()
        # hostB died and was blacklisted; the hostA survivor finished
        # alone at size 1.
        assert results == [1]

    def test_ray_transport_terminates_removed_workers(self, fake_ray):
        # Unit-level: handles route termination through their agent.
        tr = RayTransport(fake_ray)
        h = tr.execute([sys.executable, "-c", "import time; time.sleep(60)"],
                       env={"HOROVOD_HOSTNAME": "127.0.0.1",
                            "PATH": os.environ.get("PATH", "")},
                       prefix="t")
        assert h.poll() is None
        tr.terminate([h])
        deadline = time.time() + 10
        while h.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        assert h.poll() is not None
        tr.shutdown()
