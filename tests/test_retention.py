"""Power-retention layers (`attn_kind="retention"`, models/decode.py)
against the plain reference (benchmark/reference/retention.py: the
attention form, float32 "highest", no state, no chunks), at a small size
on the CPU with seeded random weights.  The decay gate's bias is set so
that a token keeps 0.62 to 0.95 of the state: decay matters at these
lengths; the q/k norm scales are random, so the learned scale matters.

Tolerance of every comparison of logits: 1e-4 absolute on logits of
magnitude 3.  Program and reference are both float32 here and differ in
the ORDER of their sums only (a recurrence over a state and chunks
against one pass over all keys; different groupings inside XLA:CPU's
products): the widest gap seen is 2e-5 (a lone first token whose weight
is near the normaliser's eps), and a bfloat16 state or product
(2^-9 relative on sums of magnitude 1 to 10) lands at 1e-2, so 1e-4
passes the one and fails the other.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import retention as ref
from horovod_tpu.common.exceptions import (HorovodTpuError,
                                           InvalidRequestError)
from horovod_tpu.models import (TransformerConfig, init_decode_cache,
                                make_decode_step, make_train_step,
                                transformer_beam_search,
                                transformer_decode_step, transformer_extend,
                                transformer_generate, transformer_init,
                                transformer_prefill, transformer_ref_apply,
                                transformer_speculative_generate)
from horovod_tpu.models.decode import (RETENTION_CHUNK, _phi, _phi_rows,
                                       cache_leaves, retention_features)
from horovod_tpu.serve import InferenceServer
from test_spans import _profiled

TOL = 1e-4
V = 64


@pytest.fixture(scope="module", autouse=True)
def start_without_others_programs():
    """Let go of what the files before this one compiled in the same
    process.  After test_torch_shim or test_faults and then test_serve
    in ONE process (xdist's choice, by timing), XLA's CPU compiler dies
    of a segmentation fault in `backend_compile_and_load` inside this
    file, at PR 39 as at PR 40; after either alone, or with their
    executables dropped first, it does not (CHANGES.md, PR 40, has the
    runs and the trace)."""
    jax.clear_caches()


def make(seed=0, **kw):
    base = dict(vocab_size=V, d_model=32, n_heads=4, d_head=8, d_ff=64,
                n_layers=2, n_kv_heads=2, compute_dtype=jnp.float32,
                attn_kind="retention")
    base.update(kw)
    cfg = TransformerConfig(**base)
    params = transformer_init(jax.random.PRNGKey(seed), cfg)
    b = params["blocks"]
    rng = np.random.RandomState(seed + 100)
    b["b_decay"] = jnp.broadcast_to(
        jnp.linspace(0.5, 3.0, cfg.kv_heads, dtype=jnp.float32),
        b["b_decay"].shape)
    for n in ("q_norm", "k_norm"):
        b[n] = {"scale": jnp.asarray(
            rng.uniform(0.5, 1.5, b[n]["scale"].shape), jnp.float32)}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(scope="module")
def served(request, model):
    """The model a server test runs: `model`, whose state of 40 features
    the einsums step, or one with heads of 128, whose state tiles and is
    stepped by the kernel of ops/retention_step.py (interpreted here)."""
    if request.param == "einsums":
        return model
    cfg, params = make(d_head=128)
    from horovod_tpu.ops import retention_step
    assert retention_step.takes(init_decode_cache(cfg, 3, 1)["s"])
    return cfg, params


both_passes = pytest.mark.parametrize("served", ["einsums", "kernel"],
                                      indirect=True)


def reference(cfg, params, tokens):
    m = dict(num_hidden_layers=cfg.n_layers, rope_theta=cfg.rope_theta)
    return np.asarray(ref.forward(params, jnp.asarray(tokens), m))


def tokens_of(n, seed=1):
    return np.random.RandomState(seed).randint(0, V, size=n)


def prefill(cfg, params, prompt, chunk=4):
    """Chunks of 4 by default, so that the tests' short prompts cross
    chunk boundaries (the program's own length is 256)."""
    return transformer_prefill(params, init_decode_cache(cfg, 1, 1),
                               jnp.asarray(prompt)[None], cfg, chunk=chunk)


# -- the feature map -------------------------------------------------------

@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_phi_inner_product_is_the_squared_product(d):
    rng = np.random.RandomState(d)
    q = rng.randn(7, d).astype(np.float32)
    k = rng.randn(7, d).astype(np.float32)
    fq, fk = np.asarray(_phi(q), np.float64), np.asarray(_phi(k), np.float64)
    assert fq.shape == (7, retention_features(d)) == (7, (d // 2 + 1) * d)
    want = (q.astype(np.float64) * k).sum(-1) ** 2 / d
    # float32 products summed in float64: 1e-6 relative is their rounding
    np.testing.assert_allclose((fq * fk).sum(-1), want, rtol=2e-6,
                               atol=1e-6)


def test_phi_through_the_mxu_is_the_same_features():
    """The prefill's form for bfloat16 models: the rolled copies come
    out of a 0/1 matrix product over u split in two bfloat16 halves, so
    each feature equals `_phi`'s to 2^-16 of its size (the low half's
    own rounding), far under the bfloat16 it is rounded to afterwards."""
    rng = np.random.RandomState(3)
    u = (rng.randn(5, 3, 16) * 3).astype(np.float32)
    want = np.asarray(_phi(u))
    got = np.asarray(_phi_rows(jnp.asarray(u), jnp.bfloat16))
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    np.testing.assert_array_equal(
        np.asarray(_phi_rows(jnp.asarray(u), jnp.float32)), want)


# -- against the plain reference -------------------------------------------

def test_prefill_logits_equal_the_reference(model):
    cfg, params = model
    toks = tokens_of(13)
    lg, cache = prefill(cfg, params, toks)
    want = reference(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(lg)[0], want[-1], atol=TOL)
    assert set(cache) == {"s", "z", "pos"} and int(cache["pos"]) == 13


def test_prefill_then_decode_equals_the_full_forward(model):
    """11 tokens prefilled, 9 decoded through the state: the logits at
    EVERY position equal the reference's one pass over all 20."""
    cfg, params = model
    toks = tokens_of(20)
    want = reference(cfg, params, toks)
    lg, cache = prefill(cfg, params, toks[:11])
    np.testing.assert_allclose(np.asarray(lg)[0], want[10], atol=TOL)
    for n in range(11, 20):
        lg, cache = transformer_decode_step(
            params, cache, jnp.asarray(toks[n:n + 1]), cfg)
        np.testing.assert_allclose(np.asarray(lg)[0], want[n], atol=TOL)
    assert int(cache["pos"]) == 20


def test_decode_alone_equals_the_reference(model):
    """No prefill at all: the recurrence from an empty state."""
    cfg, params = model
    toks = tokens_of(6, seed=3)
    want = reference(cfg, params, toks)
    cache = init_decode_cache(cfg, 1, 1)
    for n in range(6):
        lg, cache = transformer_decode_step(
            params, cache, jnp.asarray(toks[n:n + 1]), cfg)
        np.testing.assert_allclose(np.asarray(lg)[0], want[n], atol=TOL)


# -- the chunked prefill ---------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 3, 4, 11, 64])
def test_chunk_length_does_not_change_the_state(model, chunk):
    """11 tokens in chunks of 1, 3 (a last chunk of 2), 4 (of 3), 11 and
    one chunk longer than the prompt: the state, the normaliser and the
    logits equal those of the token-by-token recurrence.  Tolerance: the
    state's entries are sums of at most 11 float32 products of magnitude
    under 10, grouped differently: 1e-5."""
    cfg, params = model
    toks = tokens_of(11, seed=5)
    cache = init_decode_cache(cfg, 1, 1)
    for n in range(11):
        want_lg, cache = transformer_decode_step(
            params, cache, jnp.asarray(toks[n:n + 1]), cfg)
    lg, got = prefill(cfg, params, toks, chunk)
    np.testing.assert_allclose(np.asarray(got["s"]), np.asarray(cache["s"]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["z"]), np.asarray(cache["z"]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want_lg),
                               atol=TOL)


def test_the_programs_own_chunk_length(model):
    """A prompt of 300 tokens under the program's own chunk length (256:
    one whole chunk and a last one of 44) gives the reference's logits.
    Tolerance: sums of 300 float32 terms, grouped by chunk."""
    cfg, params = model
    assert RETENTION_CHUNK == 256
    toks = tokens_of(300, seed=13)
    lg, _ = prefill(cfg, params, toks, RETENTION_CHUNK)
    np.testing.assert_allclose(np.asarray(lg)[0],
                               reference(cfg, params, toks)[-1], atol=TOL)


def test_gqa_groups_share_one_state(model):
    """Two query heads to a kv head: the state has kv heads, not query
    heads, and a model whose k, v and gate weights are the kv heads'
    repeated (4 kv heads, two and two alike) gives the same logits."""
    cfg, params = model
    cache = init_decode_cache(cfg, 3, 1)
    assert cache["s"].shape == (2, 3, 2, retention_features(8), 8)
    assert cache["z"].shape == (2, 3, 2, retention_features(8))
    assert cache["s"].dtype == cache["z"].dtype == jnp.float32
    mha = dataclasses.replace(cfg, n_kv_heads=4)
    b = dict(params["blocks"])
    for n, axis in (("wk", 2), ("wv", 2), ("w_decay", 2), ("b_decay", 1)):
        b[n] = jnp.repeat(b[n], 2, axis=axis)
    wide = dict(params, blocks=b)
    toks = tokens_of(9, seed=7)
    lg, _ = prefill(cfg, params, toks)
    lg_wide, wide_cache = prefill(mha, wide, toks)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_wide),
                               atol=TOL)
    s = np.asarray(wide_cache["s"])
    np.testing.assert_array_equal(s[:, :, 0], s[:, :, 1])


def test_rows_at_their_own_depths(model):
    """Vector `pos`: three rows behind prompts of 3, 7 and 12 tokens in
    one step give what each gives alone."""
    cfg, params = model
    prompts = [tokens_of(n, seed=n) for n in (3, 7, 12)]
    alone = [prefill(cfg, params, p)[1] for p in prompts]
    batch = {n: jnp.concatenate([c[n] for c in alone], axis=1)
             for n in ("s", "z")}
    batch["pos"] = jnp.asarray([3, 7, 12], jnp.int32)
    feed = jnp.asarray([5, 6, 7], jnp.int32)
    lg, batch = transformer_decode_step(params, batch, feed, cfg)
    for r, c in enumerate(alone):
        want, _ = transformer_decode_step(params, c, feed[r:r + 1], cfg)
        # the same arithmetic a row, batched or not: float32 round-off
        np.testing.assert_allclose(np.asarray(lg)[r], np.asarray(want)[0],
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(batch["pos"]), [4, 8, 13])


def test_bfloat16_state_is_told_from_float32(model):
    """The configuration states a float32 state: held in bfloat16 the
    same model lands outside the tolerance that float32 meets."""
    cfg, params = model
    toks = tokens_of(20, seed=9)
    want = reference(cfg, params, toks)
    half = dataclasses.replace(cfg, state_dtype=jnp.bfloat16)
    lg, cache = prefill(half, params, toks[:11])
    assert cache["s"].dtype == jnp.bfloat16
    worst = 0.0
    for n in range(11, 20):
        lg, cache = transformer_decode_step(
            params, cache, jnp.asarray(toks[n:n + 1]), half)
        worst = max(worst, float(np.abs(np.asarray(lg)[0] - want[n]).max()))
    assert 10 * TOL < worst < 0.5


# -- through the server ----------------------------------------------------

def serve(cfg, params, requests, max_batch, stagger=True):
    srv = InferenceServer(params, cfg, max_seq_tokens=40,
                          max_batch=max_batch)
    ids, done = {}, []
    for prompt, n in requests:
        ids[srv.submit(prompt, n)] = (prompt, n)
        if stagger:
            done += srv.step()
    done += srv.run()
    return srv, ids, {s.req.req_id: list(s.generated) for s in done}


@both_passes
def test_server_serves_what_generate_gives_alone(served):
    """Seven requests through three rows, admitted a step apart: each
    gets the tokens `transformer_generate` gives it alone, the state is
    held once and no page is ever allocated."""
    cfg, params = served
    rng = np.random.RandomState(1)
    requests = [(rng.randint(0, V, size=rng.randint(3, 14)), n)
                for n in (3, 6, 4, 5, 7, 2, 4)]
    srv, ids, got = serve(cfg, params, requests, max_batch=3)
    assert len(got) == 7
    for rid, (prompt, n) in ids.items():
        want, _ = transformer_generate(params, cfg,
                                       jnp.asarray(prompt)[None], n)
        assert got[rid] == np.asarray(want)[0].tolist(), rid
    assert srv.state_installs == 7
    assert srv.state_bytes == srv.view_k.nbytes + srv.view_v.nbytes \
        == 3 * srv.pool.row_bytes
    assert srv.view_k.shape[1] == 3          # a slot a row
    assert srv.pool.pages_needed(10 ** 6) == 0   # no page is counted
    assert srv.pool.utilization() == 0.0     # every row given back
    on_device = [a for a in jax.tree_util.tree_leaves(vars(srv.pool))
                 if isinstance(a, jax.Array)]
    assert sum(a.nbytes for a in on_device) == srv.state_bytes \
        and len(on_device) == 2              # no second copy anywhere


def test_server_prompt_longer_than_a_chunk(model):
    """The server's prefill crosses a chunk boundary (300 tokens, chunks
    of 256) and the decode steps go on from the installed state: the
    tokens `transformer_generate` gives, and the reference's own where
    its best logit leads the next by more than the tolerance."""
    cfg, params = model
    prompt = tokens_of(300, seed=17)
    srv = InferenceServer(params, cfg, max_seq_tokens=310, max_batch=2)
    rid = srv.submit(prompt, 5)
    (seq,) = srv.run()
    assert seq.req.req_id == rid
    want, _ = transformer_generate(params, cfg, jnp.asarray(prompt)[None], 5)
    assert list(seq.generated) == np.asarray(want)[0].tolist()
    full = np.concatenate([prompt, seq.generated])
    ref_lg = reference(cfg, params, full[:-1])[299:]
    for tok, row in zip(seq.generated, ref_lg):
        top = np.sort(row)[-2:]
        if top[1] - top[0] > 10 * TOL:
            assert tok == int(row.argmax())


@both_passes
def test_logits_read_between_steps_are_the_states_read_out(served):
    """What the benchmark's `state_error` rests on (`served_again`,
    benchmark/runners/retention_serve.py): the request's row of
    `last_logits` between two steps is what the decode step read out of
    the row's state.  Beside two other rows: the rows it returns chose
    the tokens it returns, they are the reference's logits at the same
    positions, and each read pulled the logits from the device once."""
    from benchmark.runners.retention_serve import served_again

    cfg, params = served
    srv = InferenceServer(params, cfg, max_seq_tokens=40, max_batch=3)
    for seed, n in ((31, 20), (32, 9)):
        srv.submit(tokens_of(7, seed=seed), n)
    srv.step()
    assert srv.logit_fetches == 0
    prompt = tokens_of(14, seed=33)
    tokens, logits = served_again(srv, prompt, 12)
    assert logits.shape == (11, V) and srv.logit_fetches == 11
    assert tokens[1:] == logits.argmax(-1).tolist()
    want, _ = transformer_generate(params, cfg, jnp.asarray(prompt)[None], 12)
    assert tokens == np.asarray(want)[0].tolist()
    full = np.concatenate([prompt, tokens])
    np.testing.assert_allclose(
        logits, reference(cfg, params, full[:-1])[len(prompt):],
        rtol=TOL, atol=TOL)


def test_admission_counts_rows_and_no_pages(model):
    """`StateSlots` counts no page and has no page event, whatever a
    request's budget; what is held is what the scheduler holds, and the
    free-pages gauge is not written (0 there reads as a stall)."""
    from horovod_tpu.metrics import catalog as _met
    cfg, params = model
    srv = InferenceServer(params, cfg, max_seq_tokens=40, max_batch=3)
    for budget in (1, 40, 10 ** 9):
        assert srv.pool.pages_needed(budget) == 0, budget
        assert srv.pool.can_board(budget), budget
    page_events = []
    srv.pool.on_event = lambda *ev: page_events.append(ev)
    _met.serve_pool_pages_free.set(123.0)
    for n in (4, 6, 5, 3):
        srv.submit(tokens_of(n, seed=n), 4)
    srv.step()
    assert len(srv.sched.active) == 3 and srv.sched.queue_depth() == 1
    assert srv.pool.utilization() == 1.0
    srv.run()                               # flushes the gauges
    assert srv.pool.utilization() == 0.0 and page_events == []
    assert _met.serve_pool_pages_free._solo()._value == 123.0
    assert _met.serve_state_bytes._solo()._value == srv.state_bytes > 0


def test_row_reused_carries_nothing_over(model):
    """One row, two requests one after the other: the second is served
    as if the first had never been (its prefill writes the row's slot
    whole), and an idle row's state does not leak into a later one."""
    cfg, params = model
    a, b = tokens_of(12, seed=21), tokens_of(5, seed=22)
    _, ids, got = serve(cfg, params, [(a, 6), (b, 6)], max_batch=1,
                        stagger=False)
    (_, _), (rid_b, _) = sorted(ids.items())
    want, _ = transformer_generate(params, cfg, jnp.asarray(b)[None], 6)
    assert got[rid_b] == np.asarray(want)[0].tolist()
    # the slot itself: after b's admission it holds b's prefill, bitwise
    srv = InferenceServer(params, cfg, max_seq_tokens=40, max_batch=2)
    srv.submit(a, 3)
    srv.run()
    srv.submit(b, 2)
    srv._admit()
    _, alone = prefill(cfg, params, b, RETENTION_CHUNK)   # as the server
    np.testing.assert_array_equal(np.asarray(srv.view_k[:, 0]),
                                  np.asarray(alone["s"][:, 0]))
    np.testing.assert_array_equal(np.asarray(srv.view_v[:, 0]),
                                  np.asarray(alone["z"][:, 0]))


def test_state_install_span_and_counters(model, tmp_path):
    """`hvd.serve.state_install` lies inside `admit`, once a request,
    with the request, the row and the bytes written; `pages` reads 0."""
    cfg, params = model
    (srv, ids, _), spans = _profiled(tmp_path, lambda: serve(
        cfg, params, [(tokens_of(4), 2), (tokens_of(6), 3)], max_batch=2))
    installs = [s for s in spans if s[0] == "hvd.serve.state_install"]
    admits = [s for s in spans if s[0] == "hvd.serve.admit"]
    prefills = [s for s in spans if s[0] == "hvd.serve.prefill"]
    assert len(installs) == 2 == srv.state_installs
    assert sorted(s[3]["req"] for s in installs) == sorted(ids)
    for s in installs:
        assert s[3]["bytes"] == srv.pool.row_bytes and "row" in s[3]
        assert any(a[1] <= s[1] and s[2] <= a[2] for a in admits)
        assert any(p[1] <= s[1] and s[2] <= p[2] for p in prefills)
    assert all(p[3]["pages"] == p[3]["scratch_pages"] == 0 for p in prefills)


# -- what a state cannot do yet raises, by name ----------------------------

def test_server_refuses_quantize_and_speculation(model):
    cfg, params = model
    with pytest.raises(InvalidRequestError, match="quantize"):
        InferenceServer(params, cfg, max_seq_tokens=16, quantize="int8")
    with pytest.raises(InvalidRequestError, match="draft_params"):
        InferenceServer(params, cfg, max_seq_tokens=16,
                        draft_params=params, draft_cfg=cfg)


@pytest.mark.parametrize("what", ["quantize", "extend", "speculative",
                                  "beam", "sharded"])
def test_decode_entry_points_refuse_by_name(model, what, mesh):
    cfg, params = model
    prompt = jnp.asarray(tokens_of(4))[None]
    with pytest.raises(InvalidRequestError, match="retention"):
        if what == "quantize":
            init_decode_cache(cfg, 1, 1, quantize="int8")
        elif what == "extend":
            transformer_extend(params, init_decode_cache(cfg, 1, 1),
                               prompt, cfg)
        elif what == "speculative":
            transformer_speculative_generate(params, cfg, params, cfg,
                                             prompt, 4)
        elif what == "beam":
            transformer_beam_search(params, cfg, prompt, 4)
        else:
            make_decode_step(mesh, cfg)


def test_training_refuses_the_kind(model, mesh):
    import optax
    cfg, params = model
    with pytest.raises(HorovodTpuError, match="not trained"):
        make_train_step(mesh, cfg, optax.sgd(0.1))
    with pytest.raises(HorovodTpuError, match="retention"):
        transformer_ref_apply(params, jnp.zeros((1, 4), jnp.int32), cfg)


@pytest.mark.parametrize("kw,match", [
    (dict(attn_kind="linear"), "attn_kind"),
    (dict(attn_kind="retention", attn_window=8), "window"),
    (dict(attn_kind="retention", d_head=7), "even")])
def test_config_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**kw)


def test_softmax_configurations_are_as_they_were():
    """The kind is off by default: no new leaf, the same cache, the same
    programs (a configuration's hash keys the program caches)."""
    cfg = TransformerConfig(vocab_size=V, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=2)
    assert cfg.attn_kind == "softmax" and cache_leaves(cfg) == ("k", "v")
    blocks = transformer_init(jax.random.PRNGKey(0), cfg)["blocks"]
    assert set(blocks) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "wi",
                           "wg", "wd"}
    assert set(init_decode_cache(cfg, 1, 4)) == {"k", "v", "pos"}
