"""chip_smoke.py off the chip: every phase function at a tiny size on the
8-device CPU mesh, the refusal to run on a platform that is not a TPU,
and the compile-cache helper the smoke and hvd.init() share."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
import chip_smoke  # noqa: E402

from horovod_tpu.common import util  # noqa: E402
from horovod_tpu.ops import pallas_kernels  # noqa: E402

TINY_LM = dict(vocab_size=64, d_model=32, n_heads=4, d_head=8, d_ff=64,
               n_layers=1)


def test_collectives_and_resnet_phase():
    assert chip_smoke.phase_collectives() == {"size": 8, "allreduce": 3.5}
    rec = chip_smoke.phase_resnet(depth=18, classes=10, image=32,
                                  per_chip=2, steps=2)
    assert rec["global_batch"] == 16
    assert rec["losses"][-1] < rec["losses"][0]


def test_transformer_phases_one_device_and_sharded():
    cfg = chip_smoke.TransformerConfig(**TINY_LM)
    one = chip_smoke.phase_transformer(cfg, 4, 32, 2, jax.devices()[:1],
                                       dp=1)
    tp, sp = chip_smoke.phase_transformer_sharded(cfg, 4, 32, 2, one)
    assert tp["mesh"] == {"dp": 4, "tp": 2}
    assert sp["mesh"] == {"dp": 4, "sp": 2}


def test_a_loss_that_does_not_fall_or_is_not_finite_fails_the_phase():
    chip_smoke._falling([2.0, 2.5, 1.9], "x")
    for losses in ([2.0, 2.0], [2.0, float("nan")], [float("inf"), 1.0]):
        with pytest.raises(chip_smoke.SmokeFailure, match="phase x"):
            chip_smoke._falling(losses, "x")


def test_server_phase():
    cfg = chip_smoke.TransformerConfig(compute_dtype=jnp.float32, **TINY_LM)
    rec = chip_smoke.phase_server(cfg, prompt_lens=(4, 9), n_requests=4,
                                  max_new=(3, 5), max_batch=2,
                                  page_tokens=4, tol=1e-4)
    assert rec["tokens"] == 3 + 5 + 3 + 5
    # f32 on the CPU is token-exact against transformer_generate
    assert rec["tokens_equal_to_generate"] == "16/16"


def test_decode_layout_phase_reports_both_sizes():
    cfg = chip_smoke.TransformerConfig(**TINY_LM)
    rec = chip_smoke.phase_decode_layout(cfg, rows=4, slots=64, kv_heads=2)
    # head-major: [L, rows, Hkv, slots, Dh]; a layer's slice in bf16
    assert rec["view"] == [1, 4, 2, 64, 8]
    assert rec["layer_k_slice_bytes"] == 4 * 2 * 64 * 8 * 2
    assert rec["temp_bytes"] > 0        # the CPU's is reported, not held


def test_kernels_phase_interpreted_on_the_cpu_mesh():
    assert pallas_kernels._interpret() is True
    # one flash case that takes every mask path at once
    errs = chip_smoke.phase_kernels(128, 0, {"flash all masks": dict(
        H=4, D=32, kv_heads=2, window=48, segments=2)}, {"decode": dict(
            slots=320, group=6, window=300, pos=[700, 0, 130], kv_heads=2,
            d_head=16, block=128)}, {"state": dict(
                live=[0, 1, 0], kv_heads=1, group=2)})
    assert set(errs) == {"flash all masks", "decode", "state",
                         "pallas_matmul", "adasum pair"}


def test_chip_smoke_refuses_the_cpu_and_names_the_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    # in this process ...
    assert util.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # ... and in another one, started somewhere else: the same fixed path
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd="/",
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "platform=cpu device_kind=cpu" in r.stdout
    assert f"compile_cache={want}" in r.stdout
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_dir_from_outside_is_left_alone(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert util.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
