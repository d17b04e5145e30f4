"""CPU rehearsal of `run.py` at a tiny preset (see tiny.py): the last
line's keys, that `--seed` changes token ids and nothing else, that the
lower-precision control comes out as not correct, and that a timed path
broken underneath makes `correct` false.  A CPU run gives counts and
correctness, never a time: no number of these runs is a metric."""
import importlib
import io
import json
import time

import jax
import numpy as np
import pytest

from benchmark.lib import harness, traffic
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def run(root, workload, seed, seconds=1.0):
    out = io.StringIO()
    rc = harness.run_cell(root, workload, seed, seconds, False,
                          time.perf_counter(), require_chip=False,
                          peaks=tiny.PEAKS, out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, lines, json.loads(lines[-1])


def build(root, workload, seed):
    _, cell, config, tr, _ = harness.find_cell(root, workload)
    mod = importlib.import_module("benchmark.runners." + config["runner"])
    ctx = harness.Context(root=root, cell=cell, config=config, traffic=tr,
                          seed=seed, devices=jax.devices()[:cell["chips"]],
                          peaks=tiny.PEAKS)
    return mod.Runner(ctx)


ONE_CHIP = ["mistral7b_train_4k", "mistral7b_doc_saturated",
            "mistral7b_chat_steady"]


needs_four = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="set XLA_FLAGS=--xla_force_host_platform_device_count=4")


@pytest.mark.parametrize("workload", ONE_CHIP + [
    pytest.param("resnet50_dp4", marks=needs_four)])
def test_last_line(root, workload):
    rc, lines, last = run(root, workload, 3000000019)
    assert rc == 0
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert "setup_s" in last["metrics"] and len(last["metrics"]) == 2
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert any(l.startswith("check ") and "limit" in l for l in lines)


def test_plan_does_not_depend_on_the_seed(root):
    """Lengths, order and due times come from the traffic file alone."""
    for name in ("doc_saturated", "chat_steady"):
        tr = harness.load_json(f"{root}/bench/traffic/{name}.json")
        a, b = traffic.plan(tr), traffic.plan(tr)
        assert a == b and len(a.requests) > 10
        # every block of the grid holds every pair: any window sees the mix
        block = sum(p["weight"] for p in tr["pairs"])
        first = a.requests[:block]
        assert {(r.prompt_len, r.output_len) for r in first} == set(a.pairs)
    r = a.requests[3]
    x = traffic.prompt_tokens(1, r, 128)
    assert not np.array_equal(x, traffic.prompt_tokens(2, r, 128))
    assert np.array_equal(x, traffic.prompt_tokens(1, r, 128))


def test_seeds_issue_the_same_work(root):
    """Two seeds: the same requests in the same order with the same due
    times, and every request emits exactly its output length."""
    seen = []
    for seed in (11, 4000000007):
        r = build(root, "mistral7b_chat_steady", seed)
        r.window(1.0)
        reqs = sorted((t.plan.index, t.plan.prompt_len, t.plan.due_s,
                       len(t.seq.generated))
                      for t in r.finished if 0 <= t.plan.index
                      and t.plan.due_s < 1.0)
        assert all(n == r.plan.requests[i].output_len
                   for i, _, _, n in reqs)
        seen.append(reqs)
        tokens = [tuple(t.prompt) for t in r.finished if t.plan.index == 0]
        seen.append(tokens)
    assert seen[0] == seen[2] and len(seen[0]) > 5
    assert seen[1] != seen[3]          # the token ids do differ


@pytest.mark.parametrize("workload", ["mistral7b_train_4k",
                                      "mistral7b_chat_steady"])
def test_lower_precision_control_is_not_correct(root, workload):
    r = build(root, workload, 5)
    r.window(0.5)
    got = r.readings("fp8")
    assert all(c.ok for c in got["program"]), got["program"]
    assert not all(c.ok for c in got["control"]), got["control"]


def test_step_that_leaves_its_state_unchanged(root, monkeypatch):
    """The rest of a run over a training step broken underneath."""
    from horovod_tpu import models

    real = models.make_train_step

    def broken(mesh, cfg, opt, *a, **k):
        step, shard_state, shard_batch = real(mesh, cfg, opt, *a, **k)
        return (lambda p, s, b: (p, s, step(
            jax.tree_util.tree_map(lambda x: x + 0, p),
            jax.tree_util.tree_map(lambda x: x + 0, s), b)[2]),
            shard_state, shard_batch)

    monkeypatch.setattr(models, "make_train_step", broken)
    rc, lines, last = run(root, "mistral7b_train_4k", 7)
    assert rc == 0 and last["correct"] is False
    assert any("parameters' change" in l and "NOT CORRECT" in l
               for l in lines)


def test_token_altered_where_it_is_produced(root, monkeypatch):
    """The rest of a run over a server whose chosen tokens are altered."""
    from horovod_tpu.serve import server as server_mod

    real = server_mod.InferenceServer._plain_step

    def broken(self, rows, feed):
        real(self, rows, feed)
        self.last_logits = -self.last_logits      # argmax picks the worst

    monkeypatch.setattr(server_mod.InferenceServer, "_plain_step", broken)
    rc, lines, last = run(root, "mistral7b_doc_saturated", 7)
    assert rc == 0 and last["correct"] is False
    assert any("widest gap" in l and "NOT CORRECT" in l for l in lines)


@needs_four
def test_exchange_between_chips_left_out(root, monkeypatch):
    """The rest of a run over a trainer whose gradients are never
    averaged: every chip goes its own way."""
    import horovod_tpu as hvd

    monkeypatch.setattr(hvd, "DistributedOptimizer", lambda opt, **k: opt)
    rc, lines, last = run(root, "resnet50_dp4", 7)
    assert rc == 0 and last["correct"] is False
    assert any("copies differ" in l and "NOT CORRECT" in l for l in lines)
