"""ops/decode_attention.py, interpreted on the CPU: the kernel that reads
each row's live blocks against the einsum over every slot
(models/decode.py `_attend_view`) on the same inputs, the shape rule that
picks between them in `_decode_layer`, and a server on a view of three
blocks against the einsum server."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (TransformerConfig, init_decode_cache,
                                transformer_decode_step, transformer_init,
                                transformer_prefill)
from horovod_tpu.models import decode as D
from horovod_tpu.ops import decode_attention as DA

BLOCK = 128     # the kernel's block in the cases below (its own is 512)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, d_head=8, d_ff=64,
                n_layers=2, n_kv_heads=2, compute_dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def _inputs(B, g, S, dtype, Hkv=2, Dh=16, L=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Hkv, g, Dh), dtype),
            jax.random.normal(ks[1], (L, B, Hkv, S, Dh), dtype),
            jax.random.normal(ks[2], (L, B, Hkv, S, Dh), dtype))


def _einsum(q, ck, cv, layer, pos, window):
    pos = jnp.asarray(pos, jnp.int32)
    o = D._attend_view(q[:, None], ck, cv, layer, pos, pos[:, None],
                       _cfg(attn_window=window))
    return np.asarray(o[:, 0])


def _kernel(q, ck, cv, layer, pos, window):
    return np.asarray(jax.jit(lambda *a: DA.decode_attention(
        *a, window=window, block=BLOCK))(
            q, ck, cv, layer, jnp.asarray(pos, jnp.int32)))


# S in blocks of 128; `pos` a row (0: idle)
CASES = {
    "ragged": dict(S=512, pos=[5, 200, 383, 130]),
    "idle_rows_beside_live": dict(S=384, pos=[0, 300, 0, 17]),
    "all_idle": dict(S=256, pos=[0, 0, 0]),
    "last_block_partly_live": dict(S=384, pos=[129, 257, 300, 383]),
    "block_boundaries": dict(S=384, pos=[126, 127, 128, 255]),
    "S_no_multiple_of_block": dict(S=320, pos=[5, 300, 319, 257]),
    "wrapped_ring_under_window": dict(S=256, pos=[1000, 256, 255, 511],
                                      window=200),
    "window_inside_unwrapped_ring": dict(S=384, pos=[350, 40, 0, 200],
                                         window=100),
    "g6": dict(S=384, pos=[77, 0, 383, 260], g=6),
    "g6_S_no_multiple": dict(S=328, pos=[327, 2, 150, 0], g=6),
    "bf16": dict(S=384, pos=[300, 77, 129, 0], dtype=jnp.bfloat16,
                 tol=2e-2),
    "one_row_scalar_layer1": dict(S=256, pos=[255], layer=1),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_matches_einsum(case):
    """Online softmax over the live blocks == softmax over every slot,
    masked: same mask arithmetic, so also on a wrapped ring."""
    c = dict(CASES[case])
    pos, S = c.pop("pos"), c.pop("S")
    g, window = c.pop("g", 4), c.pop("window", 0)
    dtype, tol = c.pop("dtype", jnp.float32), c.pop("tol", 2e-6)
    layer = c.pop("layer", 0)
    q, ck, cv = _inputs(len(pos), g, S, dtype)
    got = _kernel(q, ck, cv, layer, pos, window)
    want = _einsum(q, ck, cv, layer, pos, window)
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_stale_slots_change_nothing():
    """A row freed and boarded again keeps the old request's K and V past
    its `pos` (serve/pool.py never clears a view's row): whatever lies
    there, finite, the output is the same to the bit."""
    pos = [130, 0, 383, 5]
    q, ck, cv = _inputs(len(pos), 4, 384, jnp.float32)
    dead = (jnp.arange(384)[None, :] > jnp.asarray(pos)[:, None])
    dead = dead[None, :, None, :, None]
    junk_k = jnp.where(dead, 1e4, ck)
    junk_v = jnp.where(dead, -3e4, cv)
    np.testing.assert_array_equal(_kernel(q, ck, cv, 1, pos, 0),
                                  _kernel(q, junk_k, junk_v, 1, pos, 0))


def _warm(cfg, params, B, S, T0, pos):
    cache = init_decode_cache(cfg, B, S)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T0), 0, 64)
    _, cache = transformer_prefill(params, cache, prompt, cfg)
    return {**cache, "pos": jnp.asarray(pos, jnp.int32)}


def _n_kernels(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("S,quantize,kernels", [
    (1024, None, 1), (1536, None, 1),
    (1023, None, 0),            # under two blocks
    (1028, None, 0),            # off the leaves' 8-slot tiles
    (512, None, 0), (1024, "int8", 0),
], ids=["two_blocks", "three_blocks", "under_two", "off_tile", "one_block",
        "quantized"])
def test_shape_rule(S, quantize, kernels):
    """`c`, the leaves' type and `S` pick the read, and nothing else: a
    plain ring of two blocks or more takes the kernel (once, inside the
    scan over layers), a chunk and everything smaller the einsum."""
    cfg = _cfg()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    cache = init_decode_cache(cfg, 2, S, quantize=quantize)
    cache["pos"] = jnp.asarray([3, 0], jnp.int32)
    toks = jnp.zeros((2,), jnp.int32)
    assert _n_kernels(lambda c, t: transformer_decode_step(
        params, c, t, cfg), cache, toks) == kernels
    assert _n_kernels(lambda c, t: D.transformer_extend(
        params, c, t, cfg), cache, jnp.zeros((2, 2), jnp.int32)) == 0


def test_scalar_and_vector_pos_bitwise_equal():
    """A scalar `pos` is a [B] of equal entries: through the kernel the
    two steps give the same logits and the same cache, bit for bit."""
    cfg = _cfg(compute_dtype=jnp.bfloat16)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    B, S, T0 = 2, 1024, 5
    toks = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 64)
    step = jax.jit(lambda c, t: transformer_decode_step(params, c, t, cfg))
    lg_s, out_s = step(_warm(cfg, params, B, S, T0, T0), toks)
    lg_v, out_v = step(_warm(cfg, params, B, S, T0, [T0, T0]), toks)
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(out_s[n]),
                                      np.asarray(out_v[n]))


def test_step_through_kernel_matches_einsum_step(monkeypatch):
    """`_decode_layer` through the rule, rows at their own depths and one
    idle, a window that binds: the logits of the step that reads every
    slot."""
    cfg = _cfg(attn_window=6)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3,), 0, 64)
    make = lambda: _warm(cfg, params, 3, 1024, 9, [9, 0, 4])
    lg, out = transformer_decode_step(params, make(), toks, cfg)
    monkeypatch.setattr(DA, "reads_live", lambda slots: False)
    ref_lg, ref_out = transformer_decode_step(params, make(), toks, cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out["v"]), np.asarray(ref_out["v"]),
                               atol=2e-5, rtol=2e-5)


def test_sharded_step_reads_live():
    """Under `make_decode_step`'s shard_map the head counts are local and
    the rule is the same: tp 2 over a ring of two blocks."""
    from jax.sharding import Mesh

    from horovod_tpu.models import make_decode_step

    cfg = _cfg()
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64)
    ref_lg, ref = transformer_prefill(
        params, init_decode_cache(cfg, 2, 1024), toks, cfg)
    bundle = make_decode_step(mesh, cfg)
    sp = bundle.shard_params(params)
    lg, sc = bundle.prefill(
        sp, bundle.shard_cache(init_decode_cache(cfg, 2, 1024)), toks)
    nxt = jnp.argmax(ref_lg, axis=-1)
    ref_lg, ref = transformer_decode_step(params, ref, nxt, cfg)
    lg, sc = bundle.step(sp, sc, bundle.shard_tokens(nxt))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               atol=3e-4, rtol=3e-4)


def _served(cfg, params, prompts, n_new):
    from horovod_tpu.serve import InferenceServer

    D._spec_step_fn.cache_clear()       # trace the step again
    srv = InferenceServer(params, cfg, max_seq_tokens=3 * DA.BLOCK,
                          max_batch=4, page_tokens=16)
    ids = [srv.submit(p, n) for p, n in zip(prompts, n_new)]
    done = {s.req.req_id: s.generated for s in srv.run()}
    return [done[i] for i in ids]


def test_served_tokens_match_einsum_server(monkeypatch):
    """`InferenceServer` on a view of three blocks (rows boarding and
    leaving, idle rows, a row reused) emits the tokens the server whose
    steps read every slot emits."""
    cfg = _cfg(n_layers=3)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    lens, n_new = [5, 40, 9, 23, 3, 17], [6, 3, 8, 4, 7, 5]
    prompts = [rng.randint(0, 64, size=n) for n in lens]
    got = _served(cfg, params, prompts, n_new)
    with monkeypatch.context() as m:
        m.setattr(DA, "reads_live", lambda slots: False)
        want = _served(cfg, params, prompts, n_new)
    D._spec_step_fn.cache_clear()
    assert [len(g) for g in got] == n_new
    assert got == want
