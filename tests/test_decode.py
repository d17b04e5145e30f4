"""KV-cache incremental decoding tests (models/decode.py): every
decode-step logit must equal the full teacher-forcing forward at that
position — the exact consistency contract between the training and
inference paths — across MHA, GQA, and windowed configs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    TransformerConfig,
    init_decode_cache,
    transformer_decode_step,
    transformer_generate,
    transformer_init,
    transformer_prefill,
    transformer_ref_apply,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                d_ff=64, n_layers=2, compute_dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


class TestDecodeStep:
    @pytest.mark.parametrize("kw", [
        {}, {"n_kv_heads": 2}, {"n_kv_heads": 1},
        {"n_kv_heads": 2, "attn_window": 5}, {"attn_window": 3},
    ], ids=["mha", "gqa2", "mqa", "gqa+window", "window"])
    def test_matches_teacher_forcing(self, kw):
        cfg = _cfg(**kw)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        full_logits, _ = transformer_ref_apply(params, toks, cfg)
        cache = init_decode_cache(cfg, 2, 12)
        step = jax.jit(
            lambda c, t: transformer_decode_step(params, c, t, cfg))
        for t in range(12):
            lg, cache = step(cache, toks[:, t])
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(full_logits[:, t]),
                atol=2e-4, rtol=2e-4, err_msg=f"position {t}")
        assert int(cache["pos"]) == 12

    def test_gqa_cache_is_smaller(self):
        big = init_decode_cache(_cfg(), 2, 16)
        small = init_decode_cache(_cfg(n_kv_heads=1), 2, 16)
        assert small["k"].size * 4 == big["k"].size

    def test_moe_decode_matches_teacher_forcing(self):
        # capacity_factor = n_experts -> training capacity drops nothing,
        # so the no-capacity decode routing must match the training
        # forward exactly.
        cfg = _cfg(moe_every=2, n_experts=4, capacity_factor=4.0)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        full, _ = transformer_ref_apply(params, toks, cfg)
        cache = init_decode_cache(cfg, 2, 8)
        step = jax.jit(
            lambda c, t: transformer_decode_step(params, c, t, cfg))
        for t in range(8):
            lg, cache = step(cache, toks[:, t])
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(full[:, t]),
                atol=3e-4, rtol=3e-4, err_msg=f"position {t}")

    def test_moe_prefill_matches_teacher_forcing(self):
        from horovod_tpu.models import transformer_prefill

        cfg = _cfg(moe_every=2, n_experts=4, capacity_factor=4.0)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, 64)
        full, _ = transformer_ref_apply(params, toks, cfg)
        cache = init_decode_cache(cfg, 2, 8)
        logits, cache = transformer_prefill(params, cache, toks, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1]),
                                   atol=3e-4, rtol=3e-4)

    def test_moe_generate_runs(self):
        cfg = _cfg(moe_every=2, n_experts=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 3), 0, 64)
        out, cache = transformer_generate(params, cfg, prompt, 5)
        assert out.shape == (1, 5) and int(cache["pos"]) == 8
        assert bool((out >= 0).all()) and bool((out < 64).all())


class TestGenerate:
    def test_greedy_chain_consistent(self):
        # Teacher-forcing the generated sequence reproduces the same
        # greedy choices the incremental path made.
        cfg = _cfg(n_kv_heads=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 64)
        out, cache = transformer_generate(params, cfg, prompt,
                                          max_new_tokens=6)
        assert out.shape == (2, 6) and int(cache["pos"]) == 10
        seq = jnp.concatenate([prompt, out], axis=1)
        logits, _ = transformer_ref_apply(params, seq, cfg)
        want = jnp.argmax(logits[:, 3:-1], axis=-1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_sampling_needs_rng_and_runs(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="rng"):
            transformer_generate(params, cfg, prompt, 3, temperature=1.0)
        out, _ = transformer_generate(params, cfg, prompt, 3,
                                      temperature=1.0,
                                      rng=jax.random.PRNGKey(0))
        assert out.shape == (1, 3)
        assert bool((out >= 0).all()) and bool((out < 64).all())

    def test_max_len_validation(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="max_len"):
            transformer_generate(params, cfg, prompt, 8, max_len=8)


def _assert_greedy_equiv(params, cfg, prompt, spec, plain, tol=5e-4):
    """Greedy equivalence up to numerical near-ties: the speculative
    chain must match the plain chain token-for-token UNLESS the first
    divergence sits on a near-tie in the target's own teacher-forced
    logits (top-2 gap within `tol`) — the chunked verify pass and the
    step-by-step chain reduce the same floats in different orders, so
    they may legitimately break an exact-noise tie differently.  Both
    chains condition on their own history after that point, so
    comparison for that row stops at the first near-tie divergence."""
    spec, plain = np.asarray(spec), np.asarray(plain)
    for b in range(spec.shape[0]):
        if (spec[b] == plain[b]).all():
            continue
        first = int(np.argmax(spec[b] != plain[b]))
        seq = jnp.concatenate(
            [prompt[b], jnp.asarray(plain[b][:first])])[None]
        logits, _ = transformer_ref_apply(params, seq, cfg)
        last = np.asarray(logits[0, -1], np.float32)
        top2 = np.sort(last)[-2:]
        gap = float(top2[1] - top2[0])
        assert gap <= tol, (
            f"row {b} diverges at new-token {first} with a clear "
            f"argmax (top-2 logit gap {gap:.2e} > tol {tol}): "
            f"spec={spec[b, first]} plain={plain[b, first]}")
        tied = np.flatnonzero(last >= top2[1] - tol)
        assert spec[b, first] in tied and plain[b, first] in tied, (
            b, first, spec[b, first], plain[b, first], tied)


class TestChunkExtendAndSpeculative:
    """transformer_extend (multi-token chunks) and speculative decoding
    (r5, beyond reference: draft-propose / target-verify with greedy
    equivalence up to numerical near-ties)."""

    def test_extend_matches_stepwise_decode(self):
        from horovod_tpu.models import transformer_extend

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 3), 0, 64)

        c1 = init_decode_cache(cfg, 2, 16)
        _, c1 = transformer_prefill(params, c1, prompt, cfg)
        lg_chunk, c1 = transformer_extend(params, c1, toks, cfg)

        c2 = init_decode_cache(cfg, 2, 16)
        _, c2 = transformer_prefill(params, c2, prompt, cfg)
        step_lgs = []
        for i in range(3):
            lg, c2 = transformer_decode_step(params, c2, toks[:, i], cfg)
            step_lgs.append(lg)
        np.testing.assert_allclose(
            np.asarray(lg_chunk), np.stack(
                [np.asarray(s) for s in step_lgs], axis=1),
            rtol=2e-5, atol=2e-5)
        assert int(c1["pos"]) == int(c2["pos"]) == 7

    def test_extend_gqa_and_quantized_cache(self):
        from horovod_tpu.models import transformer_extend

        cfg = _cfg(n_kv_heads=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, 2), 0, 64)
        for quant in (None, "int8"):
            c = init_decode_cache(cfg, 1, 12, quantize=quant)
            _, c = transformer_prefill(params, c, prompt, cfg)
            lg, c = transformer_extend(params, c, toks, cfg)
            assert lg.shape == (1, 2, 64)
            assert np.isfinite(np.asarray(lg)).all()

    def test_extend_wrap_rejected(self):
        from horovod_tpu.models import transformer_extend

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        c = init_decode_cache(cfg, 1, 6)
        _, c = transformer_prefill(params, c, prompt, cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, 3), 0, 64)
        with pytest.raises(ValueError, match="wrap"):
            transformer_extend(params, c, toks, cfg)

    def test_extend_on_wrapped_windowed_ring_rejected(self):
        # Past max_len on a WINDOWED config the chunk's slot-position
        # reconstruction anchors at its last query, so earlier queries
        # would silently attend over a truncated window — rejected
        # eagerly, even for a chunk that would not wrap the ring.
        from horovod_tpu.models import transformer_extend

        cfg = _cfg(attn_window=3)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        tok = jnp.zeros((1,), jnp.int32)
        c = init_decode_cache(cfg, 1, 4)
        for _ in range(4):                      # fill to pos == max_len
            _, c = transformer_decode_step(params, c, tok, cfg)
        assert int(c["pos"]) == 4
        chunk = jnp.zeros((1, 2), jnp.int32)    # pos%S + 2 <= S: no wrap
        with pytest.raises(ValueError, match="attn_window"):
            transformer_extend(params, c, chunk, cfg)
        # The same chunk on a WINDOWLESS config is legal (ring reuse is
        # the caller's contract there) — the rejection is window-specific.
        cfg2 = _cfg()
        c2 = init_decode_cache(cfg2, 1, 4)
        params2 = transformer_init(jax.random.PRNGKey(0), cfg2)
        for _ in range(4):
            _, c2 = transformer_decode_step(params2, c2, tok, cfg2)
        lg, _ = transformer_extend(params2, c2, chunk, cfg2)
        assert lg.shape == (1, 2, 64)

    def test_speculative_greedy_matches_plain_generate(self):
        from horovod_tpu.models import transformer_speculative_generate

        cfg = _cfg(n_layers=2)
        draft_cfg = _cfg(d_model=16, n_heads=2, d_head=8, d_ff=32,
                         n_layers=1)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        draft = transformer_init(jax.random.PRNGKey(7), draft_cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0, 64)

        plain, _ = transformer_generate(params, cfg, prompt, 12)
        spec, stats = transformer_speculative_generate(
            params, cfg, draft, draft_cfg, prompt, 12, gamma=3)
        _assert_greedy_equiv(params, cfg, prompt, spec, plain)
        assert stats["rounds"] >= 1
        assert 0.0 <= stats["accept_rate"] <= 1.0

    def test_self_speculation_accepts_everything(self):
        # Draft == target: every greedy proposal matches, so each round
        # lands gamma accepted + 1 bonus token.
        from horovod_tpu.models import transformer_speculative_generate

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        plain, _ = transformer_generate(params, cfg, prompt, 9)
        spec, stats = transformer_speculative_generate(
            params, cfg, params, cfg, prompt, 9, gamma=4)
        _assert_greedy_equiv(params, cfg, prompt, spec, plain)
        # Self-speculation agrees everywhere except genuine near-ties;
        # those are rare enough that the accept rate stays near 1.
        assert stats["accept_rate"] >= 0.9
        # 9 tokens at gamma=4: rounds of 4+1 -> ceil sizing, <= 3 rounds
        # barring a near-tie restart.
        assert stats["rounds"] <= 4

    @pytest.mark.parametrize("batch", [1, 3])
    def test_speculative_sampling_valid(self, batch):
        from horovod_tpu.models import transformer_speculative_generate

        cfg = _cfg()
        draft_cfg = _cfg(d_model=16, n_heads=2, d_head=8, d_ff=32,
                         n_layers=1)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        draft = transformer_init(jax.random.PRNGKey(7), draft_cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, 4),
                                    0, 64)
        toks, stats = transformer_speculative_generate(
            params, cfg, draft, draft_cfg, prompt, 8, gamma=3,
            temperature=0.8, rng=jax.random.PRNGKey(3))
        arr = np.asarray(toks)
        assert arr.shape == (batch, 8)
        assert ((arr >= 0) & (arr < 64)).all()

    def test_speculative_batched_matches_plain(self):
        # Min-acceptance batching: every row's output equals its own
        # target-greedy chain even when rows accept different lengths.
        from horovod_tpu.models import transformer_speculative_generate

        cfg = _cfg(n_layers=2)
        draft_cfg = _cfg(d_model=16, n_heads=2, d_head=8, d_ff=32,
                         n_layers=1)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        draft = transformer_init(jax.random.PRNGKey(7), draft_cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0, 64)
        plain, _ = transformer_generate(params, cfg, prompt, 9)
        spec, stats = transformer_speculative_generate(
            params, cfg, draft, draft_cfg, prompt, 9, gamma=3)
        _assert_greedy_equiv(params, cfg, prompt, spec, plain)
        # Batched self-speculation: all rows agree (up to near-ties) ->
        # min acceptance is full and every round lands gamma+1 tokens.
        spec2, st2 = transformer_speculative_generate(
            params, cfg, params, cfg, prompt, 9, gamma=4)
        _assert_greedy_equiv(params, cfg, prompt, spec2, plain)
        assert st2["accept_rate"] >= 0.9

    def test_accept_rule_preserves_target_dist(self):
        # The identity speculative sampling rests on: draft ~ q, accept
        # with min(1, p/q), else resample from norm(max(p-q, 0)) ==>
        # emitted token ~ p EXACTLY.  Property-tested on the extracted
        # rule with synthetic distributions (50k trials, TV < 0.02;
        # a draft-vs-target TV of ~0.5 would fail at ~25x that bound
        # if the rule leaked the draft distribution).
        from horovod_tpu.models.decode import _spec_accept

        rng = np.random.default_rng(0)
        V = 8
        p = rng.dirichlet(np.ones(V) * 0.7)
        q = rng.dirichlet(np.ones(V) * 0.7)
        assert 0.5 * np.abs(p - q).sum() > 0.2   # distinct dists
        n = 50_000
        counts = np.zeros(V)
        accepted = 0
        for _ in range(n):
            d = int(rng.choice(V, p=q))
            ok, tok = _spec_accept(d, p, q, rng)
            counts[tok] += 1
            accepted += ok
        hist = counts / n
        tv = 0.5 * np.abs(hist - p).sum()
        assert tv < 0.02, tv
        # Acceptance probability equals sum min(p, q) in expectation.
        expect_acc = np.minimum(p, q).sum()
        assert abs(accepted / n - expect_acc) < 0.02

    def test_speculative_rejects_bad_configs(self):
        from horovod_tpu.models import transformer_speculative_generate

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        wcfg = _cfg(attn_window=8)
        with pytest.raises(ValueError, match="attn_window"):
            transformer_speculative_generate(
                params, cfg, params, wcfg, prompt, 4)
        vcfg = _cfg(vocab_size=32)
        vparams = transformer_init(jax.random.PRNGKey(2), vcfg)
        with pytest.raises(ValueError, match="vocab"):
            transformer_speculative_generate(
                params, cfg, vparams, vcfg, prompt, 4)
        # Undersized explicit max_len must raise eagerly: inside jit the
        # ring-wrap guard cannot fire and the write would silently clamp.
        with pytest.raises(ValueError, match="max_len"):
            transformer_speculative_generate(
                params, cfg, params, cfg, prompt, 8, gamma=3,
                max_len=10)
        with pytest.raises(ValueError, match="temperature"):
            transformer_speculative_generate(
                params, cfg, params, cfg, prompt, 4, temperature=-1.0,
                rng=jax.random.PRNGKey(0))


class TestRingCacheAndPrefill:
    def test_prefill_matches_teacher_forcing(self):
        from horovod_tpu.models import transformer_prefill

        cfg = _cfg(n_kv_heads=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        full, _ = transformer_ref_apply(params, toks, cfg)
        cache = init_decode_cache(cfg, 2, 16)
        logits, cache = transformer_prefill(params, cache, toks, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1]),
                                   atol=2e-4, rtol=2e-4)
        assert int(cache["pos"]) == 10
        # decode continues seamlessly from the prefilled cache
        nxt = jnp.argmax(logits, axis=-1)
        lg2, cache = transformer_decode_step(params, cache, nxt, cfg)
        seq = jnp.concatenate([toks, nxt[:, None]], axis=1)
        full2, _ = transformer_ref_apply(params, seq, cfg)
        np.testing.assert_allclose(np.asarray(lg2),
                                   np.asarray(full2[:, -1]),
                                   atol=2e-4, rtol=2e-4)

    def test_ring_rolls_with_window(self):
        # max_len == window: decode 3x the capacity; logits stay equal
        # to the full teacher-forcing forward because the band only ever
        # needs the surviving slots.
        cfg = _cfg(attn_window=4)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        T = 12
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, T), 0, 64)
        full, _ = transformer_ref_apply(params, toks, cfg)
        cache = init_decode_cache(cfg, 2, 4)     # ring capacity = window
        step = jax.jit(
            lambda c, t: transformer_decode_step(params, c, t, cfg))
        for t in range(T):
            lg, cache = step(cache, toks[:, t])
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(full[:, t]),
                atol=2e-4, rtol=2e-4, err_msg=f"position {t}")
        assert int(cache["pos"]) == T

    def test_windowless_ring_wrap_detectable_via_pos(self):
        # decode_step past max_len without a window: the API contract is
        # that callers size max_len to the sequence; `pos` exceeding the
        # ring capacity is the observable signal of misuse.
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        cache = init_decode_cache(cfg, 1, 4)
        tok = jnp.zeros((1,), jnp.int32)
        for _ in range(5):
            _, cache = transformer_decode_step(params, cache, tok, cfg)
        assert int(cache["pos"]) == 5 > cache["k"].shape[3]      # slots

    def test_windowed_generate_with_small_ring(self):
        cfg = _cfg(attn_window=4)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 4), 0, 64)
        out, cache = transformer_generate(params, cfg, prompt, 10,
                                          max_len=4)
        assert out.shape == (1, 10) and int(cache["pos"]) == 14

    def test_ring_smaller_than_window(self):
        # A cache smaller than the window is legal as long as the ring
        # never wraps (r4 advisor): init accepts it, a NON-wrapping
        # generate works, and a WRAPPING generate is rejected eagerly.
        cfg = _cfg(attn_window=8)
        init_decode_cache(cfg, 1, 4)           # no raise
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 2), 0, 64)
        out, cache = transformer_generate(params, cfg, prompt, 2,
                                          max_len=4)
        assert out.shape == (1, 2) and int(cache["pos"]) == 4
        with pytest.raises(ValueError, match="wraps the ring"):
            transformer_generate(params, cfg, prompt, 6, max_len=4)

    def test_short_ring_matches_full_cache_when_not_wrapping(self):
        # Same tokens whether the cache is exactly-sized (< window) or
        # generously sized: a non-wrapping short ring changes nothing.
        cfg = _cfg(attn_window=8)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 3), 0, 64)
        out_short, _ = transformer_generate(params, cfg, prompt, 3,
                                            max_len=6)
        out_full, _ = transformer_generate(params, cfg, prompt, 3,
                                           max_len=32)
        assert (np.asarray(out_short) == np.asarray(out_full)).all()

    def test_prefill_requires_fresh_cache(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 4), 0, 64)
        cache = init_decode_cache(cfg, 1, 16)
        _, warm = transformer_prefill(params, cache, prompt, cfg)
        with pytest.raises(ValueError, match="fresh cache"):
            transformer_prefill(params, warm, prompt, cfg)


class TestShardedDecode:
    """make_decode_step: KV-cache decode over a dp x tp mesh must equal
    single-device decode bit-for-near (distributed inference)."""

    def _mesh(self, **shape):
        from jax.sharding import Mesh

        n = 1
        for v in shape.values():
            n *= v
        if len(jax.devices()) < n:
            pytest.skip(f"needs {n} virtual devices")
        devs = np.array(jax.devices()[:n]).reshape(*shape.values())
        return Mesh(devs, tuple(shape.keys()))

    @pytest.mark.parametrize("shape,kw", [
        ({"dp": 2, "tp": 2}, {}),
        ({"tp": 2}, {"n_kv_heads": 2}),
        ({"dp": 2}, {"moe_every": 2, "n_experts": 2}),
        ({"tp": 2}, {"moe_every": 2, "n_experts": 2}),
    ], ids=["dp2tp2", "tp2-gqa", "dp2-moe", "tp2-moe"])
    def test_matches_single_device(self, shape, kw):
        from horovod_tpu.models import make_decode_step

        cfg = _cfg(**kw)
        mesh = self._mesh(**shape)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64)

        # single-device reference chain
        ref_cache = init_decode_cache(cfg, 2, 10)
        from horovod_tpu.models import transformer_prefill
        ref_lg, ref_cache = transformer_prefill(params, ref_cache,
                                                toks, cfg)

        step, prefill, shard_params, shard_cache, shard_tokens, _ = \
            make_decode_step(mesh, cfg)
        sp = shard_params(params)
        sc = shard_cache(init_decode_cache(cfg, 2, 10))
        lg, sc = prefill(sp, sc, toks)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                                   atol=3e-4, rtol=3e-4)
        nxt = jnp.argmax(lg, axis=-1)
        for _ in range(3):
            ref_lg, ref_cache = transformer_decode_step(
                params, ref_cache, nxt, cfg)
            lg, sc = step(sp, sc, shard_tokens(nxt))
            np.testing.assert_allclose(np.asarray(lg),
                                       np.asarray(ref_lg),
                                       atol=3e-4, rtol=3e-4)
            nxt = jnp.argmax(lg, axis=-1)

    def test_sharded_extend_matches_single_device(self):
        # The speculative verify pass at dp2 x tp2: chunked extend over
        # the sharded cache equals the single-device chunk.
        from horovod_tpu.models import make_decode_step, transformer_extend

        cfg = _cfg(n_kv_heads=2)
        mesh = self._mesh(dp=2, tp=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        chunk = jax.random.randint(jax.random.PRNGKey(2), (2, 3), 0, 64)

        ref_cache = init_decode_cache(cfg, 2, 10)
        _, ref_cache = transformer_prefill(params, ref_cache, toks, cfg)
        ref_lg, ref_cache = transformer_extend(params, ref_cache,
                                               chunk, cfg)

        bundle = make_decode_step(mesh, cfg)
        sp = bundle.shard_params(params)
        sc = bundle.shard_cache(init_decode_cache(cfg, 2, 10))
        _, sc = bundle.prefill(sp, sc, toks)
        lg, sc = bundle.extend(sp, sc, chunk)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                                   atol=3e-4, rtol=3e-4)
        assert int(jax.device_get(sc["pos"])) == \
            int(ref_cache["pos"]) == 7

    def test_unsupported_axes_raise(self):
        from horovod_tpu.models import make_decode_step

        mesh = self._mesh(sp=2)
        with pytest.raises(NotImplementedError, match="dp/tp"):
            make_decode_step(mesh, _cfg())
        mesh = self._mesh(ep=2)
        with pytest.raises(NotImplementedError, match="ep"):
            make_decode_step(mesh, _cfg(moe_every=2, n_experts=2))


class TestTopP:
    def test_top_p_validation(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="top_p"):
            transformer_generate(params, cfg, prompt, 2, temperature=1.0,
                                 top_p=0.0, rng=jax.random.PRNGKey(0))

    def test_top_p_small_is_greedy(self):
        # top_p -> 0+ keeps only the argmax token, so sampling at any
        # temperature reproduces the greedy chain.
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, 64)
        greedy, _ = transformer_generate(params, cfg, prompt, 5)
        nucleus, _ = transformer_generate(params, cfg, prompt, 5,
                                          temperature=2.0, top_p=1e-6,
                                          rng=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(nucleus),
                                      np.asarray(greedy))

    def test_eos_pads_tail(self):
        # Force a guaranteed eos hit: eos_id = the greedy chain's own
        # second token; everything strictly after its first occurrence
        # must read eos_id, positions up to and including it unchanged.
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, 64)
        plain, _ = transformer_generate(params, cfg, prompt, 8)
        eos = int(plain[0, 1])
        stopped, _ = transformer_generate(params, cfg, prompt, 8,
                                          eos_id=eos)
        got = np.asarray(stopped[0])
        ref = np.asarray(plain[0])
        first = int(np.argmax(ref == eos))
        np.testing.assert_array_equal(got[: first + 1], ref[: first + 1])
        assert (got[first + 1:] == eos).all()

    def test_eos_absent_is_noop_and_validated(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, 64)
        plain, _ = transformer_generate(params, cfg, prompt, 6)
        # Pick an id the greedy chain never emits.
        unused = next(v for v in range(64)
                      if v not in np.asarray(plain).ravel())
        same, _ = transformer_generate(params, cfg, prompt, 6,
                                       eos_id=unused)
        np.testing.assert_array_equal(np.asarray(same),
                                      np.asarray(plain))
        with pytest.raises(ValueError, match="eos_id"):
            transformer_generate(params, cfg, prompt, 2, eos_id=999)

    def test_top_k_one_is_greedy(self):
        # top_k=1 keeps only the argmax token: sampling at any
        # temperature reproduces the greedy chain exactly.
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, 64)
        greedy, _ = transformer_generate(params, cfg, prompt, 5)
        topk, _ = transformer_generate(params, cfg, prompt, 5,
                                       temperature=2.0, top_k=1,
                                       rng=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(topk),
                                      np.asarray(greedy))

    def test_top_k_validation(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="top_k"):
            transformer_generate(params, cfg, prompt, 2, temperature=1.0,
                                 top_k=-1, rng=jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="top_k"):
            transformer_generate(params, cfg, prompt, 2, temperature=1.0,
                                 top_k=10_000, rng=jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="temperature"):
            transformer_generate(params, cfg, prompt, 2, top_k=4)

    def test_top_k_tokens_stay_in_top_k(self):
        # Every sampled token must be within the top-k of the model's
        # own distribution at its position (teacher-forced check).
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, 64)
        out, _ = transformer_generate(params, cfg, prompt, 8,
                                      temperature=3.0, top_k=2,
                                      rng=jax.random.PRNGKey(11))
        seq = jnp.concatenate([prompt, out], axis=1)
        logits, _ = transformer_ref_apply(params, seq, cfg)
        for i in range(8):
            pos = prompt.shape[1] - 1 + i
            top2 = np.argsort(-np.asarray(logits[0, pos]))[:2]
            assert int(out[0, i]) in top2, (i, int(out[0, i]), top2)

    def test_top_k_with_top_p_runs(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        out, _ = transformer_generate(params, cfg, prompt, 4,
                                      temperature=1.0, top_p=0.9,
                                      top_k=8, rng=jax.random.PRNGKey(3))
        arr = np.asarray(out)
        assert arr.shape == (1, 4)
        assert ((arr >= 0) & (arr < 64)).all()

    def test_top_p_sampling_runs(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        out, _ = transformer_generate(params, cfg, prompt, 4,
                                      temperature=1.0, top_p=0.9,
                                      rng=jax.random.PRNGKey(0))
        assert out.shape == (1, 4)
        assert bool((out >= 0).all()) and bool((out < 64).all())


class TestBeamSearch:
    def test_width_one_equals_greedy(self):
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        greedy, _ = transformer_generate(params, cfg, prompt, 6)
        beams, scores = transformer_beam_search(params, cfg, prompt, 6,
                                                beam_width=1)
        assert beams.shape == (2, 1, 6)
        np.testing.assert_array_equal(np.asarray(beams[:, 0]),
                                      np.asarray(greedy))

    def test_eos_freezes_beam_score_and_tail(self):
        # Pick eos = a token inside the plain best beam: with eos_id
        # set, that beam's tail after its first eos must read eos and
        # its score must equal the teacher-forced logprob sum up to and
        # INCLUDING the first eos (forced continuations add 0).
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        N, W = 6, 3
        plain, _ = transformer_beam_search(params, cfg, prompt, N,
                                           beam_width=W)
        eos = int(plain[0, 0, 2])
        beams, scores = transformer_beam_search(params, cfg, prompt, N,
                                                beam_width=W,
                                                eos_id=eos)
        arr = np.asarray(beams)
        # Non-vacuity: the chosen eos must actually appear somewhere.
        assert any(eos in arr[0, b] for b in range(W)), arr
        for b in range(W):
            row = arr[0, b]
            if eos in row:
                first = int(np.argmax(row == eos))
                assert (row[first:] == eos).all(), (b, row)
                # Teacher-forced score of the truncated chain.
                seq = jnp.concatenate(
                    [prompt, jnp.asarray(row[: first + 1])[None]],
                    axis=1)
                logits, _ = transformer_ref_apply(params, seq, cfg)
                lp = jax.nn.log_softmax(logits, axis=-1)
                picked = jnp.take_along_axis(
                    lp[:, 3:-1], seq[:, 4:, None].astype(jnp.int32),
                    -1)[..., 0]
                np.testing.assert_allclose(
                    float(scores[0, b]), float(picked.sum()),
                    rtol=2e-4, atol=2e-4)

    def test_eos_length_penalty_uses_actual_lengths(self):
        # Reported scores must equal the teacher-forced raw chain
        # logprob (to first eos) divided by the ACTUAL length —
        # a uniform max_new normalization fails this whenever any
        # beam finished early.
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        N, W = 6, 3
        plain, _ = transformer_beam_search(params, cfg, prompt, N,
                                           beam_width=W)
        eos = int(plain[0, 0, 2])
        beams, scores = transformer_beam_search(
            params, cfg, prompt, N, beam_width=W, eos_id=eos,
            length_penalty=1.0)
        arr = np.asarray(beams)
        lengths = []
        for b in range(W):
            row = arr[0, b]
            first = (int(np.argmax(row == eos)) if eos in row else N - 1)
            length = first + 1
            lengths.append(length)
            seq = jnp.concatenate(
                [prompt, jnp.asarray(row[: length])[None]], axis=1)
            logits, _ = transformer_ref_apply(params, seq, cfg)
            lp = jax.nn.log_softmax(logits, axis=-1)
            raw = float(jnp.take_along_axis(
                lp[:, 3:-1], seq[:, 4:, None].astype(jnp.int32),
                -1)[..., 0].sum())
            np.testing.assert_allclose(float(scores[0, b]),
                                       raw / length,
                                       rtol=3e-4, atol=3e-4)
        # Non-vacuity: at least one beam must have finished early.
        assert min(lengths) < N, lengths
        # Output stays sorted best-first after the re-sort.
        s = np.asarray(scores[0])
        assert (np.diff(s) <= 1e-6).all(), s
        with pytest.raises(ValueError, match="eos_id"):
            transformer_beam_search(params, cfg, prompt, 4,
                                    beam_width=2, eos_id=999)

    def test_scores_are_true_chain_logprobs(self):
        # Each returned beam's score must equal the sum of the chosen
        # tokens' logprobs under teacher forcing of that beam.
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        N = 5
        beams, scores = transformer_beam_search(params, cfg, prompt, N,
                                                beam_width=3)
        for w in range(3):
            seq = jnp.concatenate([prompt, beams[:, w]], axis=1)
            logits, _ = transformer_ref_apply(params, seq, cfg)
            lp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                lp[:, 3:-1], seq[:, 4:, None].astype(jnp.int32),
                axis=-1)[..., 0]
            want = float(picked.sum())
            assert abs(want - float(scores[0, w])) < 5e-3, (w, want,
                                                            scores)

    def test_best_beam_at_least_greedy(self):
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 3), 0, 64)
        N = 6
        _, s1 = transformer_beam_search(params, cfg, prompt, N,
                                        beam_width=1)
        _, s4 = transformer_beam_search(params, cfg, prompt, N,
                                        beam_width=4)
        assert bool((s4[:, 0] >= s1[:, 0] - 1e-5).all())

    def test_width_validation(self):
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="beam_width"):
            transformer_beam_search(params, cfg, prompt, 2, beam_width=0)


class TestGenerateValidation:
    def test_top_p_without_temperature_rejected(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="temperature"):
            transformer_generate(params, cfg, prompt, 2, top_p=0.9)


class TestQuantizedCache:
    """int8 KV cache: ~1/4 the bytes, per-vector max-abs scales, decode
    logits within quantization noise of the full-precision path."""

    def test_cache_bytes_quartered(self):
        # Realistic head dim (64): scale overhead is 4/64 per element.
        cfg = _cfg(d_head=64, d_model=256)   # compute_dtype f32
        full = init_decode_cache(cfg, 2, 16)
        q8 = init_decode_cache(cfg, 2, 16, quantize="int8")
        full_bytes = full["k"].size * 4
        q8_bytes = q8["k"]["q"].size + q8["k"]["scale"].size * 4
        assert q8_bytes < full_bytes / 3.5

    def test_decode_close_to_full_precision(self):
        cfg = _cfg(n_kv_heads=2)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        cf = init_decode_cache(cfg, 2, 10)
        cq = init_decode_cache(cfg, 2, 10, quantize="int8")
        stepf = jax.jit(
            lambda c, t: transformer_decode_step(params, c, t, cfg))
        stepq = jax.jit(
            lambda c, t: transformer_decode_step(params, c, t, cfg))
        worst = 0.0
        for t in range(10):
            lf, cf = stepf(cf, toks[:, t])
            lq, cq = stepq(cq, toks[:, t])
            denom = float(jnp.max(jnp.abs(lf))) or 1.0
            worst = max(worst,
                        float(jnp.max(jnp.abs(lf - lq))) / denom)
        assert worst < 0.05, worst        # int8 noise, not divergence
        assert worst > 0.0                # and genuinely quantized

    def test_generate_and_beam_with_int8(self):
        from horovod_tpu.models import transformer_beam_search

        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        out, cache = transformer_generate(params, cfg, prompt, 5,
                                          quantize="int8")
        assert out.shape == (1, 5)
        assert cache["k"]["q"].dtype == jnp.int8
        beams, scores = transformer_beam_search(
            params, cfg, prompt, 5, beam_width=2, quantize="int8")
        assert beams.shape == (1, 2, 5)

    def test_sharded_int8_matches_single_device(self):
        from jax.sharding import Mesh
        from horovod_tpu.models import make_decode_step

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        cfg = _cfg(n_kv_heads=2)
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 64)
        ref_cache = init_decode_cache(cfg, 2, 8, quantize="int8")
        from horovod_tpu.models import transformer_prefill
        ref_lg, ref_cache = transformer_prefill(params, ref_cache,
                                                toks, cfg)
        step, prefill, shard_params, shard_cache, shard_tokens, _ = \
            make_decode_step(mesh, cfg, quantize="int8")
        sp = shard_params(params)
        sc = shard_cache(init_decode_cache(cfg, 2, 8, quantize="int8"))
        lg, sc = prefill(sp, sc, toks)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                                   atol=3e-4, rtol=3e-4)
        nxt = jnp.argmax(lg, axis=-1)
        lg2, sc = step(sp, sc, shard_tokens(nxt))
        ref_lg2, ref_cache = transformer_decode_step(params, ref_cache,
                                                     nxt, cfg)
        np.testing.assert_allclose(np.asarray(lg2),
                                   np.asarray(ref_lg2),
                                   atol=3e-4, rtol=3e-4)

    def test_fp8_cache_close_to_full_precision(self):
        cfg = _cfg()
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 64)
        cf = init_decode_cache(cfg, 1, 8)
        cq = init_decode_cache(cfg, 1, 8, quantize="fp8_e4m3")
        assert cq["k"]["q"].dtype == jnp.float8_e4m3fn
        worst = 0.0
        for t in range(8):
            lf, cf = transformer_decode_step(params, cf, toks[:, t], cfg)
            lq, cq = transformer_decode_step(params, cq, toks[:, t], cfg)
            denom = float(jnp.max(jnp.abs(lf))) or 1.0
            worst = max(worst,
                        float(jnp.max(jnp.abs(lf - lq))) / denom)
        assert 0.0 < worst < 0.08, worst   # e4m3 ~2 mantissa bits

    def test_bad_quantize_rejected(self):
        with pytest.raises(ValueError, match="quantize"):
            init_decode_cache(_cfg(), 1, 8, quantize="fp4")


def test_sharded_fp8_cache_builds_and_steps():
    from jax.sharding import Mesh
    from horovod_tpu.models import make_decode_step

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = _cfg(n_kv_heads=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
    step, prefill, shard_params, shard_cache, shard_tokens, _ = \
        make_decode_step(mesh, cfg, quantize="fp8_e4m3")
    sp = shard_params(params)
    sc = shard_cache(init_decode_cache(cfg, 2, 6, quantize="fp8_e4m3"))
    lg, sc = prefill(sp, sc, toks)
    lg, sc = step(sp, sc, shard_tokens(jnp.argmax(lg, axis=-1)))
    assert bool(jnp.isfinite(lg).all())


# -- the in-place layer walk (PR 27) ---------------------------------------

def _jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, scan, ...) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


def _walk_reference(params, cache, tokens, cfg, mode, decode_layer=None):
    """The walk as a plain per-layer loop: each layer's attention from
    `_decode_layer` / `_prefill_layer` (or `decode_layer`, of
    `_decode_layer`'s signature) on THAT layer's slice of the cache (a
    one-layer stack, index 0), the slices joined afterwards."""
    from horovod_tpu.models import decode as D
    from horovod_tpu.models.transformer import (
        _is_moe_layer, _mlp_block, _rmsnorm)

    tm = jax.tree_util.tree_map
    dt = cfg.compute_dtype
    x = params["embed"][tokens].astype(dt)
    if tokens.ndim == 1:
        x = x[:, None, :]
    ks, vs, moe_idx = [], [], 0
    for i in range(cfg.n_layers):
        lp = tm(lambda p: p[i], params["blocks"])
        cki = tm(lambda a: a[i:i + 1], cache["k"])
        cvi = tm(lambda a: a[i:i + 1], cache["v"])
        if mode == "prefill":
            x, cki, cvi = D._prefill_layer(lp, cki, cvi, 0, x, cfg)
        else:
            x, cki, cvi = (decode_layer or D._decode_layer)(
                lp, cki, cvi, 0, x, cache["pos"], cfg)
        ks.append(cki)
        vs.append(cvi)
        if _is_moe_layer(cfg, i):
            mp = tm(lambda p: p[moe_idx], params["moe"])
            x = D._moe_tokens(mp, lp["ln2"]["scale"], x, cfg)
            moe_idx += 1
        else:
            x = _mlp_block(lp, x, cfg, None)
    if mode == "prefill":
        x = x[:, -1:]
    x = _rmsnorm(params["final_norm"]["scale"], x)
    logits = jnp.einsum("bod,vd->bov", x.astype(dt),
                        params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    if mode != "chunk":
        logits = logits[:, 0]
    join = lambda *a: jnp.concatenate(a, axis=0)
    return logits, {"k": tm(join, *ks), "v": tm(join, *vs)}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)),
            np.asarray(y.astype(jnp.float32)))


@pytest.mark.parametrize("arch", ["dense", "moe"])
@pytest.mark.parametrize("quantize", [None, "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
@pytest.mark.parametrize("mode", ["scalar", "vector", "chunk", "prefill"])
def test_layer_walk_in_place(mode, quantize, arch):
    """The layer walk's contract, for every caller and cache layout:
    the cache rides in the scan's carry (never xs / ys), only the new
    slots are written, the compiled program consumes the donated cache,
    and the result is bitwise the per-layer reference."""
    from horovod_tpu.models import decode as D
    from horovod_tpu.serve.server import _prefill_fn

    kw = dict(n_kv_heads=2, compute_dtype=jnp.bfloat16, n_layers=3)
    if arch == "moe":
        kw.update(moe_every=2, n_experts=2, n_layers=4)
    cfg = _cfg(**kw)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    B, S, T0 = 2, 12, 5
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T0), 0, 64)

    def warm(pos):
        c = init_decode_cache(cfg, B, S, quantize=quantize)
        _, c = transformer_prefill(params, c, prompt, cfg)
        return {"k": c["k"], "v": c["v"],
                "pos": jnp.asarray(pos, jnp.int32)}

    if mode == "prefill":
        fn, toks = _prefill_fn(cfg), prompt
        make = lambda: init_decode_cache(cfg, B, S, quantize=quantize)
    elif mode == "chunk":
        fn = D._spec_extend_fn(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, 3), 0, 64)
        make = lambda: warm([T0, 3])
    else:
        fn = D._spec_step_fn(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 64)
        make = lambda: warm(T0 if mode == "scalar" else [T0, 3])
    n_new = toks.size // B              # slots a row fills in a layer

    # -- structure: what the walk does with the cache ---------------------
    cache = make()
    pos0 = np.asarray(cache["pos"])
    held = jax.tree_util.tree_leaves((cache["k"], cache["v"]))
    leaf_shapes = {a.shape for a in held}
    eqns = list(_jaxpr_eqns(jax.make_jaxpr(fn)(params, cache, toks).jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"]
    if arch == "dense":
        (scan,) = scans                 # the one walk over the layers
        nc, nk = scan.params["num_consts"], scan.params["num_carry"]
        carry = {v.aval.shape for v in scan.invars[nc:nc + nk]}
        xs = {v.aval.shape for v in scan.invars[nc + nk:]}
        ys = {v.aval.shape for v in scan.outvars[nk:]}
        assert leaf_shapes <= carry
        assert not leaf_shapes & (xs | ys)      # stacked [L, ...] both
    else:
        assert not scans                # static indices, same contract
    writes = [e for e in eqns
              if e.primitive.name in ("scatter", "dynamic_update_slice")
              and e.invars[0].aval.shape in leaf_shapes]
    assert len(writes) == len(held) * (1 if arch == "dense"
                                       else cfg.n_layers)
    for e in writes:                    # B x n_new vectors, no more
        upd = e.invars[-1 if e.primitive.name == "scatter" else 1].aval
        full = e.invars[0].aval.shape
        # full is [L, B, Hkv, S, (Dh)]: a vector is Hkv pieces of Dh
        assert upd.size == B * n_new * full[2] * int(np.prod(full[4:])), \
            (upd, full)

    # -- values: bitwise the per-layer reference; the argument is consumed --
    # Both sides are compiled to round every bf16 result (by default XLA
    # keeps f32 between the ops it fuses, so a scan and an unrolled loop
    # of the same layers differ in the last bf16 digit, on any backend).
    exact = {"xla_allow_excess_precision": False}
    ref_lg, ref_kv = jax.jit(
        lambda p, c, t: _walk_reference(p, c, t, cfg, mode)).lower(
            params, cache, toks).compile(compiler_options=exact)(
                params, cache, toks)
    run = fn.lower(params, cache, toks).compile(compiler_options=exact)
    lg, out = run(params, cache, toks)
    assert all(a.is_deleted() for a in held)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_lg))
    _leaves_equal((out["k"], out["v"]), (ref_kv["k"], ref_kv["v"]))
    np.testing.assert_array_equal(np.asarray(out["pos"]), pos0 + n_new)

    if mode == "vector":
        # `_decode_layer`'s promise: equal depths in a vector are the
        # scalar path, bit for bit.
        lg_s, out_s = fn(params, warm(T0), toks)    # scalar program
        lg_v, out_v = fn(params, warm([T0] * B), toks)
        np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
        _leaves_equal((out_s["k"], out_s["v"]), (out_v["k"], out_v["v"]))


# -- the head-major cache against a slot-major layer (PR 29) -----------------

def _slot_major_layer(lp, ck, cv, i, x, pos, cfg):
    """Layer `i`'s attention over a SLOT-MAJOR stacked cache, ck / cv
    [L, B, S, Hkv, Dh] (scales [L, B, S, Hkv]): `_decode_layer` as it read
    and wrote before the cache went head-major, kept here as the
    reference the new layout is held to.  Same projections, rope,
    quantisation, mask and operand types; only where a vector lies
    differs."""
    from horovod_tpu.models import decode as D
    from horovod_tpu.models.transformer import _rmsnorm, _rope

    dt, Dh = cfg.compute_dtype, cfg.d_head
    quant = isinstance(ck, dict)
    B, c = x.shape[:2]
    S = (ck["q"] if quant else ck).shape[2]
    h = _rmsnorm(lp["ln1"]["scale"], x)
    q, k, v = (jnp.einsum("bod,dhk->bohk", h, lp[w].astype(dt))
               for w in ("wq", "wk", "wv"))
    Hkv = k.shape[2]
    pos = jnp.asarray(pos)
    rows = pos if pos.ndim else jnp.full((B,), pos)
    positions = rows[:, None] + jnp.arange(c)[None, :]          # [B, c]
    if pos.ndim:
        rope = lambda a: D._rope_rows(a, positions, cfg.rope_theta)
    else:
        rope = lambda a: _rope(a, positions[0], cfg.rope_theta)
    q, k = rope(q).astype(dt), rope(k).astype(dt)
    at = (i, jnp.arange(B)[:, None], positions % S)

    def put(cache, val):
        if not quant:
            return cache.at[at].set(val)
        pay, scale = D._quant_vec(val, cache["q"].dtype)
        return {"q": cache["q"].at[at].set(pay),
                "scale": cache["scale"].at[at].set(scale)}

    ck, cv = put(ck, k), put(cv, v)
    lk, lv = (jax.tree_util.tree_map(lambda a: a[i], t) for t in (ck, cv))
    qg = q.reshape(B, c, Hkv, -1, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   (lk["q"] if quant else lk).astype(jnp.float32))
    if quant:
        s = s * lk["scale"].transpose(0, 2, 1)[:, :, None, None, :]
    s = s / (Dh ** 0.5)
    last = rows[:, None] + (c - 1)
    abs_pos = last - ((last - jnp.arange(S)[None, :]) % S)      # [B, S]
    valid = (abs_pos[:, None, :] >= 0) & \
        (abs_pos[:, None, :] <= positions[:, :, None])
    p = jax.nn.softmax(
        jnp.where(valid[:, None, None, :, :], s, -1e30), axis=-1)
    if quant:
        p = p * lv["scale"].transpose(0, 2, 1)[:, :, None, None, :]
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                   (lv["q"] if quant else lv).astype(jnp.float32))
    o = o.reshape(B, c, -1, Dh).astype(dt)
    out = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dt))
    return x + out.astype(x.dtype), ck, cv


@pytest.mark.parametrize("quantize", [None, "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
@pytest.mark.parametrize("pos_kind", ["scalar", "vector"])
@pytest.mark.parametrize("mode", ["step", "chunk"])
def test_head_major_cache_equals_slot_major_reference(mode, pos_kind,
                                                      quantize):
    """The cache's layout moves bytes and nothing else: a step or a chunk
    over the head-major cache gives the logits, and (its slot and head
    axes swapped back) the cache, of the slot-major layer kept above, bit
    for bit.  Both sides compiled to round every bf16 result, as in
    `test_layer_walk_in_place`."""
    from horovod_tpu.models import decode as D

    cfg = _cfg(n_kv_heads=2, compute_dtype=jnp.bfloat16, n_layers=3)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    B, S, T0 = 2, 12, 5
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T0), 0, 64)
    cache = init_decode_cache(cfg, B, S, quantize=quantize)
    _, cache = transformer_prefill(params, cache, prompt, cfg)
    cache["pos"] = jnp.asarray(T0 if pos_kind == "scalar" else [T0, 3],
                               jnp.int32)
    if mode == "step":
        fn = D._spec_step_fn(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 64)
    else:
        fn = D._spec_extend_fn(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, 3), 0, 64)
    swap = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.swapaxes(a, 2, 3), t)     # head- <-> slot-major
    slot_major = {"k": swap(cache["k"]), "v": swap(cache["v"]),
                  "pos": cache["pos"]}
    exact = {"xla_allow_excess_precision": False}
    ref_lg, ref_kv = jax.jit(lambda p, c, t: _walk_reference(
        p, c, t, cfg, mode, decode_layer=_slot_major_layer)).lower(
            params, slot_major, toks).compile(compiler_options=exact)(
                params, slot_major, toks)
    lg, out = fn.lower(params, cache, toks).compile(
        compiler_options=exact)(params, cache, toks)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_lg))
    _leaves_equal((swap(out["k"]), swap(out["v"])),
                  (ref_kv["k"], ref_kv["v"]))
