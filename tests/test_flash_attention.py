"""Flash-attention kernel tests (vs the dense oracle in
parallel/sequence.py).  Runs under the Pallas interpreter on the CPU
platform (conftest forces JAX_PLATFORMS=cpu → interpret mode), the same
CI pattern as tests/test_pallas_kernels.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import sequence as seq


def qkv(B=2, T=256, H=4, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense_oracle(self, causal):
        q, k, v = qkv()
        o_flash = fa.flash_attention(q, k, v, causal=causal)
        o_dense = seq.dense_attention_oracle(q, k, v, causal=causal)
        np.testing.assert_allclose(o_flash, o_dense, atol=2e-5, rtol=2e-5)

    def test_bf16_inputs_bf16_output(self):
        q, k, v = qkv(dtype=jnp.bfloat16)
        o = fa.flash_attention(q, k, v)
        assert o.dtype == jnp.bfloat16
        o_dense = seq.dense_attention_oracle(q, k, v, causal=True)
        np.testing.assert_allclose(
            o.astype(np.float32), o_dense.astype(np.float32), atol=3e-2)

    def test_single_block(self):
        q, k, v = qkv(T=128)
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v),
            seq.dense_attention_oracle(q, k, v, causal=True), atol=2e-5, rtol=2e-5)

    def test_unaligned_seq_raises(self):
        q, k, v = qkv(T=100)
        with pytest.raises(ValueError, match="seq len"):
            fa.flash_attention(q, k, v)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense_oracle(self, causal):
        q, k, v = qkv()

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seq.dense_attention_oracle), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=3e-5 * max(1.0, scale), rtol=1e-4,
                err_msg=f"d{name}")

    def test_grad_through_jit(self):
        q, k, v = qkv(T=128)
        f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v) ** 2)))
        g = f(q, k, v)
        assert g.shape == q.shape and bool(jnp.isfinite(g).all())


class TestBlockSizes:
    """HOROVOD_FLASH_BLOCK_Q/K (r04 kernel rework): non-default and
    asymmetric tiles must agree with the oracle, incl. the causal
    block-skip arithmetic for bq != bk."""

    @pytest.mark.parametrize("bq,bk", [(256, 256), (256, 64), (64, 256)])
    def test_fwd_bwd_match_oracle(self, bq, bk, monkeypatch):
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_Q", str(bq))
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_K", str(bk))
        q, k, v = qkv()

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True),
            seq.dense_attention_oracle(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)
        gf = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seq.dense_attention_oracle),
                      argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=3e-5 * max(1.0, scale), rtol=1e-4,
                err_msg=f"d{name}")

    def test_non_dividing_block_clamps(self, monkeypatch):
        # A requested tile that does not divide T must not break a
        # previously-working shape: 192 clamps to 128 for T=256.
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_Q", "192")
        q, k, v = qkv()
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v),
            seq.dense_attention_oracle(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)
        assert fa._block_sizes(256) == (128, 128)
        assert fa._block_sizes(384) == (128, 128)
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_Q", "256")
        assert fa._block_sizes(384) == (128, 128)   # 256 ∤ 384
        assert fa._block_sizes(512) == (256, 128)

    def test_blocks_clamp_to_short_seq(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_Q", "512")
        monkeypatch.setenv("HOROVOD_FLASH_BLOCK_K", "512")
        q, k, v = qkv(T=128)
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v),
            seq.dense_attention_oracle(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)


class TestDispatch:
    def test_full_attention_routes_to_flash_when_enabled(self, monkeypatch):
        q, k, v = qkv(T=128)
        calls = []
        real = fa.flash_attention

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        monkeypatch.setattr(fa, "flash_attention", spy)
        out = seq.full_attention(q, k, v, causal=True)
        assert calls, "flash path not taken"
        monkeypatch.delenv("HOROVOD_FLASH_ATTENTION")
        np.testing.assert_allclose(
            out, seq.dense_attention_oracle(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)

    def test_fallback_on_offset_or_unaligned(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        monkeypatch.setattr(fa, "flash_attention",
                            lambda *a, **k: pytest.fail("must not dispatch"))
        q, k, v = qkv(T=96)  # unaligned → dense path
        seq.full_attention(q, k, v, causal=True)
        q2, k2, v2 = qkv(T=128)
        seq.full_attention(q2, k2, v2, causal=True, q_offset=64)

    def test_ulysses_uses_flash_local_attention(self, monkeypatch):
        # Ulysses calls full_attention on the gathered sequence; with the
        # flag on, the local compute rides the kernel and numerics hold.
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:4])
        if len(devs) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = Mesh(devs, ("sp",))
        q, k, v = qkv(B=1, T=512, H=4, D=32)
        dense = seq.ulysses_attention(q, k, v, mesh)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        flash = seq.ulysses_attention(q, k, v, mesh)
        np.testing.assert_allclose(flash, dense, atol=2e-5, rtol=2e-5)


class TestRingFlash:
    """Ring attention with the flash kernel as the per-pair engine."""

    def _mesh(self, n=4):
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:n])
        if len(devs) < n:
            pytest.skip(f"needs {n} virtual devices")
        return Mesh(devs, ("sp",))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, causal, monkeypatch):
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=512, H=4, D=32)
        oracle = seq.dense_attention_oracle(q, k, v, causal=causal)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        out = seq.ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, oracle, atol=3e-5, rtol=3e-5)

    def test_dispatch_falls_back_on_unaligned_shard(self, monkeypatch):
        # T=256 over sp=4 -> T_local=64, not 128-aligned: XLA path.
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=256, H=4, D=32)
        # Oracle BEFORE the env flip so it is the true dense reference.
        oracle = seq.dense_attention_oracle(q, k, v)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        monkeypatch.setattr(
            fa, "flash_attention_lse",
            lambda *a, **kw: pytest.fail("must not dispatch"))
        out = seq.ring_attention(q, k, v, mesh)
        np.testing.assert_allclose(out, oracle, atol=3e-5, rtol=3e-5)

    def test_grads_match_oracle(self, monkeypatch):
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=512, H=2, D=32)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        gf = jax.grad(lambda q, k, v: jnp.sum(
            seq.ring_attention(q, k, v, mesh) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        monkeypatch.delenv("HOROVOD_FLASH_ATTENTION")
        gd = jax.grad(lambda q, k, v: jnp.sum(
            seq.dense_attention_oracle(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = max(1.0, float(jnp.abs(b).max()))
            np.testing.assert_allclose(a, b, atol=3e-5 * scale,
                                       err_msg=f"d{name}")

    def test_lse_output_matches_dense_logsumexp(self):
        q, k, v = qkv(T=128)
        _, lse = fa.flash_attention_lse(q, k, v, causal=False)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        ref = jax.scipy.special.logsumexp(s, axis=-1)  # [B,H,T]
        np.testing.assert_allclose(
            lse, ref.transpose(0, 2, 1), atol=2e-5, rtol=2e-5)


class TestAutoRouting:
    """Length-based auto routing (flash_routed): forced by the env flag
    when set; unset = TPU-only auto at T >= MIN_T (r04 on-chip sweep:
    dense OOMs at 16k, flash is the only runner)."""

    def test_forced_on_and_off(self, monkeypatch):
        from horovod_tpu.ops import flash_attention as fa

        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        assert fa.flash_routed(128) is True
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "0")
        assert fa.flash_routed(1 << 20) is False

    def test_auto_is_off_on_cpu(self, monkeypatch):
        from horovod_tpu.ops import flash_attention as fa

        monkeypatch.delenv("HOROVOD_FLASH_ATTENTION", raising=False)
        # The test harness runs on the CPU platform: auto must not
        # route to the (interpreter-slow) kernel regardless of length.
        assert fa.flash_routed(1 << 20) is False

    def test_auto_threshold_on_tpu(self, monkeypatch):
        from horovod_tpu.ops import flash_attention as fa

        monkeypatch.delenv("HOROVOD_FLASH_ATTENTION", raising=False)
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fa.flash_routed(16384) is True
        assert fa.flash_routed(8192) is False
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION_MIN_T", "4096")
        assert fa.flash_routed(8192) is True

    def test_empty_env_value_is_unset(self, monkeypatch):
        from horovod_tpu.ops import flash_attention as fa

        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "")
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # Empty string must fall through to auto, not force dense.
        assert fa.flash_routed(32768) is True


class TestGQAWindow:
    """GQA/MQA (k/v with fewer heads) and causal sliding-window — the
    long-context extensions the reference lacks entirely."""

    @pytest.mark.parametrize("hkv", [1, 2])
    def test_gqa_fwd_bwd_match_oracle(self, hkv):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (2, 256, 4, 64))
        k = jax.random.normal(ks[1], (2, 256, hkv, 64))
        v = jax.random.normal(ks[2], (2, 256, hkv, 64))
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True),
            seq.dense_attention_oracle(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seq.dense_attention_oracle),
                      argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=5e-5 * max(1.0, scale), rtol=2e-4,
                err_msg=f"d{name}")

    @pytest.mark.parametrize("window", [64, 100, 1000])
    def test_window_matches_masked_oracle(self, window):
        q, k, v = qkv(T=512)
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True, window=window),
            seq.dense_attention_oracle(q, k, v, causal=True,
                                       window=window),
            atol=2e-5, rtol=2e-5)

    def test_window_grads_match_oracle(self):
        q, k, v = qkv(T=256)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, window=96) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q, k, v: jnp.sum(
            seq.dense_attention_oracle(q, k, v, causal=True,
                                       window=96) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=5e-5 * max(1.0, scale), rtol=2e-4,
                err_msg=f"d{name}")

    def test_gqa_plus_window(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 2, 64))
        v = jax.random.normal(ks[2], (1, 256, 2, 64))
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True, window=64),
            seq.dense_attention_oracle(q, k, v, causal=True, window=64),
            atol=2e-5, rtol=2e-5)

    def test_window_requires_causal(self):
        # All three entry points agree (r4 advisor: the dense paths used
        # to silently accept the combination with different semantics).
        q, k, v = qkv(T=128)
        with pytest.raises(ValueError, match="causal"):
            fa.flash_attention(q, k, v, causal=False, window=64)
        with pytest.raises(ValueError, match="causal"):
            seq.dense_attention_oracle(q, k, v, causal=False, window=64)
        with pytest.raises(ValueError, match="causal"):
            seq.full_attention(q, k, v, causal=False, window=64)

    def test_bad_gqa_heads_raise(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64))
        k = jax.random.normal(ks[1], (1, 128, 3, 64))
        v = jax.random.normal(ks[2], (1, 128, 3, 64))
        with pytest.raises(ValueError, match="GQA"):
            fa.flash_attention(q, k, v)

    def test_oracle_gqa_window_support(self):
        # The oracle itself: window=None + equal heads is the original
        # path (regression anchor for every other test in this file).
        q, k, v = qkv(T=128)
        a = seq.dense_attention_oracle(q, k, v, causal=True)
        b = seq.dense_attention_oracle(q, k, v, causal=True, window=128)
        np.testing.assert_allclose(a, b, atol=1e-6)


class TestWindowUnderSP:
    """Sliding window across sequence-parallel shards: the XLA blockwise
    ring carries per-pair position bands; Ulysses sees the full sequence
    locally after its all_to_all."""

    def _mesh(self, n=4):
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:n])
        if len(devs) < n:
            pytest.skip(f"needs {n} virtual devices")
        return Mesh(devs, ("sp",))

    @pytest.mark.parametrize("window", [8, 100])
    def test_ring_window_matches_oracle(self, window):
        # T=256 over sp=4 -> Tl=64; window=100 crosses shard boundaries.
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=256, H=4, D=32)
        out = seq.ring_attention(q, k, v, mesh, window=window)
        ref = seq.dense_attention_oracle(q, k, v, causal=True,
                                         window=window)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def test_ulysses_window_matches_oracle(self):
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=256, H=4, D=32)
        out = seq.ulysses_attention(q, k, v, mesh, window=48)
        ref = seq.dense_attention_oracle(q, k, v, causal=True, window=48)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def test_ring_window_grads_match_oracle(self):
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=256, H=2, D=32)
        gf = jax.grad(lambda q: jnp.sum(
            seq.ring_attention(q, k, v, mesh, window=72) ** 2))(q)
        gd = jax.grad(lambda q: jnp.sum(
            seq.dense_attention_oracle(q, k, v, causal=True,
                                       window=72) ** 2))(q)
        scale = float(jnp.abs(gd).max())
        np.testing.assert_allclose(gf, gd, atol=5e-5 * max(1.0, scale),
                                   rtol=2e-4)

    def test_ring_window_zero_raises(self):
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=256, H=2, D=32)
        with pytest.raises(ValueError, match="window"):
            seq.ring_attention(q, k, v, mesh, window=0)

    def test_flash_forced_ring_still_honors_window(self, monkeypatch):
        # With HOROVOD_FLASH_ATTENTION=1 a window config must NOT route
        # to the (windowless) flash ring engine.
        mesh = self._mesh()
        q, k, v = qkv(B=1, T=512, H=2, D=32)  # Tl=128, flash-aligned
        ref = seq.dense_attention_oracle(q, k, v, causal=True, window=80)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        out = seq.ring_attention(q, k, v, mesh, window=80)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


class TestRingGQA:
    """Ring attention carries GQA kv blocks natively — the ppermute
    rotates Hkv-sized blocks (ICI bytes / group factor) and heads are
    expanded only inside the per-pair engines."""

    def _mesh(self, n=4):
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:n])
        if len(devs) < n:
            pytest.skip(f"needs {n} virtual devices")
        return Mesh(devs, ("sp",))

    @pytest.mark.parametrize("hkv", [1, 2])
    def test_xla_ring_gqa_matches_oracle(self, hkv):
        mesh = self._mesh()
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 32))
        k = jax.random.normal(ks[1], (1, 256, hkv, 32))
        v = jax.random.normal(ks[2], (1, 256, hkv, 32))
        out = seq.ring_attention(q, k, v, mesh)
        ref = seq.dense_attention_oracle(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def test_flash_ring_gqa_matches_oracle(self, monkeypatch):
        mesh = self._mesh()
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 512, 4, 32))  # Tl=128 aligned
        k = jax.random.normal(ks[1], (1, 512, 2, 32))
        v = jax.random.normal(ks[2], (1, 512, 2, 32))
        ref = seq.dense_attention_oracle(q, k, v, causal=True)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        out = seq.ring_attention(q, k, v, mesh)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def test_ring_gqa_window(self):
        mesh = self._mesh()
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 32))
        k = jax.random.normal(ks[1], (1, 256, 2, 32))
        v = jax.random.normal(ks[2], (1, 256, 2, 32))
        out = seq.ring_attention(q, k, v, mesh, window=72)
        ref = seq.dense_attention_oracle(q, k, v, causal=True, window=72)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


class TestSegmentIds:
    """Packed-sequence block-diagonal masking: tokens attend only
    within their own segment (the packed-pretraining mask the reference
    cannot express)."""

    def _packed(self, B=2, T=256, H=4, D=64, split=100):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
        seg = jnp.concatenate(
            [jnp.zeros((B, split), jnp.int32),
             jnp.ones((B, T - split), jnp.int32)], axis=1)
        return q, k, v, seg, split

    def test_kernel_matches_oracle(self):
        q, k, v, seg, _ = self._packed()
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True, segment_ids=seg),
            seq.dense_attention_oracle(q, k, v, causal=True,
                                       segment_ids=seg),
            atol=2e-5, rtol=2e-5)

    def test_packed_equals_separate(self):
        # The semantic contract: packing two documents with segment ids
        # is identical to attending each document alone.
        q, k, v, seg, split = self._packed()
        packed = fa.flash_attention(q, k, v, causal=True,
                                    segment_ids=seg)
        a = seq.dense_attention_oracle(q[:, :split], k[:, :split],
                                       v[:, :split], causal=True)
        b = seq.dense_attention_oracle(q[:, split:], k[:, split:],
                                       v[:, split:], causal=True)
        np.testing.assert_allclose(
            packed, jnp.concatenate([a, b], axis=1), atol=2e-5,
            rtol=2e-5)

    def test_grads_match_oracle(self):
        q, k, v, seg, _ = self._packed(T=128)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v, causal=True, segment_ids=seg) ** 2)

        gf = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss(seq.dense_attention_oracle),
                      argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=5e-5 * max(1.0, scale), rtol=2e-4,
                err_msg=f"d{name}")

    def test_segments_with_gqa_and_window(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 2, 64))
        v = jax.random.normal(ks[2], (1, 256, 2, 64))
        seg = (jnp.arange(256)[None] >= 130).astype(jnp.int32)
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, causal=True, window=48,
                               segment_ids=seg),
            seq.dense_attention_oracle(q, k, v, causal=True, window=48,
                                       segment_ids=seg),
            atol=2e-5, rtol=2e-5)

    def test_full_attention_routes_segments(self, monkeypatch):
        q, k, v, seg, _ = self._packed(T=128)
        monkeypatch.setenv("HOROVOD_FLASH_ATTENTION", "1")
        out = seq.full_attention(q, k, v, causal=True, segment_ids=seg)
        monkeypatch.delenv("HOROVOD_FLASH_ATTENTION")
        np.testing.assert_allclose(
            out, seq.dense_attention_oracle(q, k, v, causal=True,
                                            segment_ids=seg),
            atol=2e-5, rtol=2e-5)

    def test_bad_shape_raises(self):
        q, k, v, _, _ = self._packed(T=128)
        with pytest.raises(ValueError, match="segment_ids"):
            fa.flash_attention(q, k, v,
                               segment_ids=jnp.zeros((2, 64), jnp.int32))
