"""Boarding (serve/pool.py `PagedKVPool.board_pages`): a request boards
through a scratch cache of its PROMPT's pages, so what a boarding
compiles is keyed by the prompt's length and not by the output's, and
the pool holds afterwards, bit for bit, what a scratch of the whole
budget and a bulk write of all of it left there."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common.exceptions import InvalidRequestError
from horovod_tpu.models import (
    TransformerConfig,
    init_decode_cache,
    transformer_init,
    transformer_prefill,
)
from horovod_tpu.models.transformer import AttnSpec, LatentSpec, Rotary
from horovod_tpu.serve import InferenceServer, PagedKVPool
from horovod_tpu.serve.pool import KindKVPool, WindowedKVPool
from horovod_tpu.serve.server import _prefill_fn

PT, ROWS, VIEW_PAGES, POOL_PAGES = 4, 2, 6, 12

UNIFORM = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=1, compute_dtype=jnp.float32)
WINDOWED = TransformerConfig(
    vocab_size=64, d_model=32, d_head=8, d_ff=64, n_layers=2, n_kv_heads=2,
    compute_dtype=jnp.float32, layer_attn=("full", "sliding"),
    layer_mlp=("dense",) * 2,
    attn_specs=(("full", AttnSpec(4)), ("sliding", AttnSpec(6, 5))))
LATENT = TransformerConfig(
    vocab_size=64, d_model=32, d_head=12, d_ff=64, n_layers=1,
    compute_dtype=jnp.float32, layer_attn=("latent",), layer_mlp=("dense",),
    attn_specs=(("latent", LatentSpec(
        n_heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=12,
        rotary=Rotary(theta=1e5, yarn_factor=8, yarn_original=8),
        scale_factor=1.5)),))

CACHES = {
    "paged": (UNIFORM, lambda: PagedKVPool(
        UNIFORM, POOL_PAGES, PT, None, ROWS, VIEW_PAGES)),
    "paged-int8": (UNIFORM, lambda: PagedKVPool(
        UNIFORM, POOL_PAGES, PT, "int8", ROWS, VIEW_PAGES)),
    "windowed": (WINDOWED, lambda: WindowedKVPool(
        WINDOWED, POOL_PAGES, PT, None, ROWS, VIEW_PAGES)),
    "latent": (LATENT, lambda: KindKVPool(
        LATENT, POOL_PAGES, PT, None, ROWS, VIEW_PAGES)),
}


@functools.lru_cache(maxsize=None)
def _model(cfg):
    # weights of the right shapes from numpy: the initialiser's random
    # bits are the longest compile a model of this size has
    rng = np.random.RandomState(3)
    shapes = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(3), cfg))
    return (jax.tree_util.tree_map(
        lambda a: jnp.asarray(0.2 * rng.standard_normal(a.shape), a.dtype),
        shapes), jax.jit(lambda p, c, t: transformer_prefill(p, c, t, cfg)))


def _leaves(cache, tree):
    """The two leaves of a prefilled scratch that `cache` keeps in
    pages: a uniform model's own, a patterned model's by kind."""
    kind = getattr(cache, "paged", getattr(cache, "kind", None))
    return tuple(tree[n] if kind is None else tree[n][kind]
                 for n in cache.leaves)


def _board_through_the_budget(cache, cfg, req_id, row, n_tokens, params,
                              prompt, prefill):
    """Boarding as it was: every page of the budget zeroed, a scratch
    cache of all of them, the whole of it written into the pages."""
    pool = getattr(cache, "pool", cache)         # WindowedKVPool's pages
    pids = pool.alloc(req_id, n_tokens)
    scratch = init_decode_cache(cfg, 1, len(pids) * PT, pool.quantize)
    lg, scratch = prefill(params, scratch, jnp.asarray(prompt[None]))
    pool.seat(req_id, row, *_leaves(cache, scratch))
    return lg, scratch


def _bits(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("prompt_tokens", [8, 6],
                         ids=["whole_pages", "a_page_in_part"])
@pytest.mark.parametrize("name", list(CACHES))
def test_boarding_leaves_what_a_budget_sized_scratch_left(name,
                                                          prompt_tokens):
    """Over pages that hold something: pool and view after `board` are
    the bits a scratch of the whole budget and `scatter_pages` of all of
    it leave, the prompt's pages written and never zeroed, the others
    zeroed and never written; a window layer's ring, sized by the window
    and not by the scratch, is what that prefill left."""
    cfg, make = CACHES[name]
    params, prefill = _model(cfg)
    prompt = np.random.RandomState(prompt_tokens).randint(
        0, 64, prompt_tokens)
    new, old = make(), make()
    pools = [getattr(c, "pool", c) for c in (new, old)]
    for pool in pools:
        pool.k, pool.v = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.ones(a.shape, a.dtype)),
            (pool.k, pool.v))
    args = (1, 0, prompt_tokens + 9, params, prompt, prefill)
    logits = new.board(*args)
    want, scratch = _board_through_the_budget(old, cfg, *args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    assert pools[0].pages == pools[1].pages
    assert len(pools[0].pages[1]) > new.scratch_pages(
        prompt_tokens, prompt_tokens + 9) == 2
    new.refresh(), old.refresh()
    for a, b in zip(*(_bits((p.k, p.v, p.view)) for p in pools)):
        np.testing.assert_array_equal(a, b)
    # the prompt is there, the budget's other pages are empty, and the
    # pages nobody asked for hold what they held
    pids = pools[0].pages[1]
    for a in _bits((pools[0].k, pools[0].v)):       # [L, page, Hkv, slot]
        assert a[:, pids[:2]].any() and not a[:, pids[2:]].any()
        assert a[:, len(pids):].all()
    for ring, leaf in zip(getattr(new, "rings", ()), new.leaves):
        np.testing.assert_array_equal(         # the prompt wraps the 5
            np.asarray(ring["sliding"][:, 0]),
            np.asarray(scratch[leaf]["sliding"][:, 0]))


class _Compiles:
    """Programs built while it is open (nothing here has a persistent
    cache to fetch one from)."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        return self

    def _dur(self, event, secs, **_):
        self.n += event == "/jax/core/compile/backend_compile_duration"

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._dur)


def test_one_prefill_program_a_prompt_length():
    """Two outputs behind one prompt length share the prefill program,
    and a (prompt, output) pair that was served before compiles nothing
    at all: not the scratch, the zeroing nor the bulk write."""
    cfg = dataclasses.replace(UNIFORM, d_ff=48)     # a jit of its own
    params, _ = _model(cfg)
    srv = InferenceServer(params, cfg, max_seq_tokens=32, max_batch=2,
                          page_tokens=PT)
    prompt = np.arange(8, dtype=np.int32)

    def serve(outputs):
        for n in outputs:
            srv.submit(prompt, n)
            srv.run()

    serve([3, 11])
    assert _prefill_fn(cfg)._cache_size() == 1
    with _Compiles() as compiles:
        serve([3, 11])
    assert compiles.n == 0


@pytest.mark.parametrize("slots,why", [
    (6, "not a whole number of pages"),
    (16, "more pages than the budget"),
])
def test_scatter_pages_refuses(slots, why):
    pool = PagedKVPool(UNIFORM, POOL_PAGES, PT)
    pool.alloc(0, 12)
    cache = init_decode_cache(UNIFORM, 1, slots)
    with pytest.raises(InvalidRequestError, match="whole number of pages"):
        pool.scatter_pages(0, cache["k"], cache["v"])


def test_alloc_on_its_own_zeroes_all_it_hands_out():
    """`covered` is boarding's: without it every page comes back zero,
    whatever was there."""
    pool = PagedKVPool(UNIFORM, 3, PT)
    pool.k, pool.v = pool.k + 1, pool.v + 1
    assert pool.alloc(0, 8, covered=1) == [0, 1]
    assert np.asarray(pool.k[:, 0]).all() and not np.asarray(
        pool.k[:, 1]).any()
    pool.free(0)
    pool.alloc(1, 12)
    assert not np.asarray(pool.k).any() and not np.asarray(pool.v).any()
