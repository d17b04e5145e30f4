"""Pallas Adasum-kernel numerics under the interpreter (reference:
adasum.h DispatchComputeDotAndNormSqrds / DispatchScaledAdd inner
loops; the interpreter runs the identical kernel code the TPU compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_kernels as PK
from horovod_tpu.ops.adasum import adasum_reference


@pytest.mark.parametrize("n", [128 * 256, 128 * 256 + 1, 1000, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_dot_norms_matches_jnp(n, dtype):
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(2, n), dtype)
    b = jnp.asarray(rng.randn(2, n), dtype)
    out = PK.fused_dot_norms(a, b)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    expect = jnp.stack([
        jnp.sum(af * bf, -1), jnp.sum(af * af, -1), jnp.sum(bf * bf, -1)
    ], -1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_scaled_add(dtype):
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(3, 500), dtype)
    b = jnp.asarray(rng.randn(3, 500), dtype)
    ca = jnp.asarray([0.5, 1.0, -2.0], jnp.float32)
    cb = jnp.asarray([1.5, 0.0, 3.0], jnp.float32)
    out = PK.fused_scaled_add(ca, cb, a, b)
    expect = (ca[:, None] * a.astype(jnp.float32)
              + cb[:, None] * b.astype(jnp.float32)).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6, atol=1e-4)
    assert out.dtype == dtype


def test_pair_combine_matches_reference():
    rng = np.random.RandomState(2)
    a = rng.randn(2, 300).astype(np.float32)
    b = rng.randn(2, 300).astype(np.float32)
    out = PK.pallas_pair_combine_batched(jnp.asarray(a), jnp.asarray(b))
    for i in range(2):
        expect = adasum_reference([a[i], b[i]])
        np.testing.assert_allclose(np.asarray(out[i]), expect, rtol=1e-4)


def test_pair_combine_zero_norm_guard():
    a = jnp.zeros((1, 64), jnp.float32)
    b = jnp.ones((1, 64), jnp.float32)
    out = PK.pallas_pair_combine_batched(a, b)
    # Zero-norm side contributes via the guard coefficient 1.0: result = b.
    np.testing.assert_allclose(np.asarray(out), np.ones((1, 64)))


def test_tree_reduce_uses_pallas_when_forced(monkeypatch):
    # Force the pallas path (normally auto-off on CPU) through the full
    # Adasum tree; numerics must match the float64 reference model.
    monkeypatch.setenv("HOROVOD_ADASUM_PALLAS", "1")
    from horovod_tpu.ops.adasum import adasum_tree_reduce

    rng = np.random.RandomState(3)
    grads = rng.randn(8, 129).astype(np.float32)
    out = adasum_tree_reduce(jnp.asarray(grads))
    expect = adasum_reference(list(grads))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4)


def test_auto_gating():
    # CPU interpreter default: off unless forced.
    assert not PK.pallas_enabled(10**9)


# ---------------------------------------------------------------------------
# Fused-pipeline Pallas matmul (ops/fused_collectives.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 128, 128), (200, 300, 150),
                                   (7, 5, 3), (129, 257, 129)])
def test_pallas_matmul_matches_jnp(shape):
    from horovod_tpu.ops.fused_collectives import pallas_matmul
    m, k, n = shape
    rng = np.random.RandomState(7)
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    out = pallas_matmul(jnp.asarray(a), jnp.asarray(b))
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), a @ b,
                               rtol=1e-4, atol=1e-3)


def test_pallas_matmul_shape_mismatch_raises():
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.ops.fused_collectives import pallas_matmul
    with pytest.raises(HorovodTpuError, match="inner dims"):
        pallas_matmul(jnp.zeros((4, 5)), jnp.zeros((6, 7)))


def test_fused_pallas_gating(monkeypatch):
    from horovod_tpu.ops import fused_collectives as fc
    # Opt-in: off by default even for big operands.
    monkeypatch.delenv("HOROVOD_FUSED_PALLAS", raising=False)
    assert not fc.fused_pallas_enabled(10**9)
    # Tiny operands stay on the XLA dot even when forced.
    monkeypatch.setenv("HOROVOD_FUSED_PALLAS", "1")
    assert not fc.fused_pallas_enabled(16)
    assert fc.fused_pallas_enabled(10**9)


def test_chunk_matmul_rides_pallas_when_forced(monkeypatch):
    # The fused chunks' compute stage must route through the Pallas
    # kernel when HOROVOD_FUSED_PALLAS=1 and still match the XLA dot.
    monkeypatch.setenv("HOROVOD_FUSED_PALLAS", "1")
    from horovod_tpu.ops.fused_collectives import _chunk_matmul
    rng = np.random.RandomState(8)
    a = rng.randn(150, 140).astype(np.float32)
    b = rng.randn(140, 130).astype(np.float32)
    out = _chunk_matmul(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), a @ b,
                               rtol=1e-4, atol=1e-3)
