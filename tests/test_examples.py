"""Example-script smoke tests: every BASELINE-config example runs end to
end on the simulated mesh (reference: examples are exercised in CI docs
builds; here they are first-class tests)."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script, extra_args=(), extra_env=None, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env.update(extra_env or {})
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "examples", script), *extra_args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO_ROOT,
        env=env)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.integration
class TestExamples:
    def test_mnist(self):
        out = _run_example("mnist.py", ["--epochs", "1"])
        assert "test_acc=" in out

    def test_tape_mnist(self):
        out = _run_example("tape_mnist.py")
        assert "loss=" in out

    @pytest.mark.slow
    def test_synthetic_benchmark_tiny(self):
        out = _run_example(
            "synthetic_benchmark.py",
            ["--model", "resnet18", "--batch-size", "2",
             "--image-size", "32", "--num-warmup-batches", "1",
             "--num-batches-per-iter", "2", "--num-iters", "1"])
        assert "Total img/sec" in out

    @pytest.mark.slow
    def test_synthetic_benchmark_adasum_fp16(self):
        out = _run_example(
            "synthetic_benchmark.py",
            ["--model", "resnet18", "--batch-size", "2",
             "--image-size", "32", "--num-warmup-batches", "1",
             "--num-batches-per-iter", "1", "--num-iters", "1",
             "--use-adasum", "--fp16-allreduce"])
        assert "Total img/sec" in out

    @pytest.mark.slow
    def test_synthetic_benchmark_int8_ring(self):
        out = _run_example(
            "synthetic_benchmark.py",
            ["--model", "resnet18", "--batch-size", "2",
             "--image-size", "32", "--num-warmup-batches", "1",
             "--num-batches-per-iter", "1", "--num-iters", "1",
             "--compression", "int8"])
        assert "Total img/sec" in out

    @pytest.mark.slow
    def test_autotune_demo_tiny(self):
        out = _run_example("autotune_demo.py", ["--tiny"],
                           extra_env={"XLA_FLAGS": ""})
        assert "frozen:" in out
        assert "sample  3" in out  # warmup 1 + max_samples 3 closed out

    def test_torch_mnist(self):
        out = _run_example("torch_mnist.py", ["--epochs", "1"])
        assert "loss=" in out

    @pytest.mark.slow
    def test_spark_estimator(self):
        # Spawns its own 2 worker processes (LocalBackend pins them to
        # CPU with clean XLA_FLAGS itself).
        out = _run_example("spark_estimator.py", ["--np", "2"],
                           timeout=560)
        assert "ok" in out

    def test_transformer_lm_mesh(self):
        out = _run_example(
            "transformer_lm.py",
            ["--dp", "2", "--tp", "2", "--sp", "2", "--d-model", "64",
             "--n-layers", "2", "--n-heads", "4", "--seq-len", "32",
             "--batch-size", "4", "--steps", "2"])
        assert "tok/s" in out

    def test_transformer_lm_moe_pipeline(self):
        out = _run_example(
            "transformer_lm.py",
            ["--dp", "2", "--pp", "2", "--ep", "2", "--moe-every", "2",
             "--d-model", "64", "--n-layers", "4", "--n-heads", "4",
             "--seq-len", "33", "--batch-size", "8", "--steps", "2"])
        assert "tok/s" in out

    def test_transformer_lm_gqa_window(self):
        out = _run_example(
            "transformer_lm.py",
            ["--dp", "8", "--n-kv-heads", "2", "--attn-window", "16",
             "--d-model", "64", "--n-layers", "2", "--n-heads", "4",
             "--seq-len", "32", "--batch-size", "8", "--steps", "2"])
        assert "tok/s" in out

    def test_generate_kv_cache(self):
        out = _run_example(
            "generate.py",
            ["--n-kv-heads", "2", "--attn-window", "16", "--d-model",
             "64", "--n-layers", "2", "--n-heads", "4",
             "--new-tokens", "8"],
            extra_env={"XLA_FLAGS": ""})
        assert "generated" in out

    def test_generate_speculative(self):
        out = _run_example(
            "generate.py",
            ["--batch", "1", "--d-model", "64", "--n-layers", "2",
             "--n-heads", "4", "--new-tokens", "8", "--spec-gamma", "3",
             "--draft-d-model", "32"],
            extra_env={"XLA_FLAGS": ""})
        assert "accept rate" in out

    def test_generate_beam(self):
        out = _run_example(
            "generate.py",
            ["--d-model", "64", "--n-layers", "2", "--n-heads", "4",
             "--new-tokens", "6", "--beam", "2"],
            extra_env={"XLA_FLAGS": ""})
        assert "best score" in out

    @pytest.mark.slow
    def test_elastic_resnet_under_driver(self, tmp_path):
        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\necho localhost:1\n")
        script.chmod(0o755)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner",
             "--host-discovery-script", str(script), "--min-np", "1",
             sys.executable,
             os.path.join(REPO_ROOT, "examples", "elastic_resnet.py")],
            capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
            env=env)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        assert "epoch 3" in r.stdout


@pytest.mark.integration
class TestKerasExample:
    def test_keras_mnist(self):
        out = _run_example("keras_mnist.py",
                          ["--epochs", "1", "--n", "128",
                           "--batch-size", "32"], timeout=420)
        assert "final loss:" in out


@pytest.mark.integration
class TestNewExamples:
    def test_hierarchical_multislice(self):
        out = _run_example("hierarchical_multislice.py")
        assert "final loss" in out

    def test_executor_pool(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "examples", "executor_pool.py")],
            capture_output=True, text=True, timeout=300, cwd=REPO_ROOT,
            env=env)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        assert "pool reused" in r.stdout
