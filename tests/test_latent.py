"""A patterned model whose attention layers are of a LATENT kind
(`LatentSpec`, models/decode.py "Latent attention"): one compressed
latent and one shared rotated key a token in the cache, expanded where a
prompt is prefilled and absorbed where a row is stepped; experts routed
in groups of which a share is held (models/experts.py); served through
`InferenceServer` over `KindKVPool` (serve/pool.py).  Held against
`benchmark/reference/latent_moe.py`, the plain float32 reference, on
seeded weights at a size a test can hold."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import weights, weights_latent
from benchmark.reference import latent_moe as ref
from benchmark.runners.latent_serve import transformer_config
from horovod_tpu.common.exceptions import (HorovodTpuError,
                                           InvalidRequestError)
from horovod_tpu.metrics import catalog as met
from horovod_tpu.models import (init_decode_cache, make_decode_step,
                                make_train_step, transformer_beam_search,
                                transformer_decode_step, transformer_extend,
                                transformer_generate, transformer_init,
                                transformer_prefill,
                                transformer_speculative_generate)
from horovod_tpu.models import decode, experts
from horovod_tpu.ops import decode_attention
from horovod_tpu.serve import InferenceServer
from horovod_tpu.serve.pool import KindKVPool, WindowedKVPool, make_cache

V = 320

# GigaChat3.1-702B-A36B's shape at a size a test can hold: one leading
# dense layer, then three sparse ones; 4 heads of 16 + 8 and 24 over a
# latent of 32 and a query rank of 48; YaRN over an original context of
# 16 with mscale_all_dim 1 (the scale carries m^2 = 1.46); 32 routed
# experts in 4 groups of 8 of which 2 are kept, 4 a token, of which this
# "chip" holds the first 8, beside a shared one.
M = dict(
    vocab_size=V, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, n_shared_experts=1, n_routed_experts=8,
    router_width=32, experts_held=[0, 8], routed_scaling_factor=2.5,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=24,
    qk_nope_head_dim=16, n_group=4, topk_group=2, num_experts_per_tok=4,
    first_k_dense_replace=1, rope_theta=100000,
    rope_scaling={"beta_fast": 4, "beta_slow": 1, "factor": 8, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16,
                  "rope_type": "yarn"},
    assumed={"router_bias_std": 0.02, "route_eps": 1e-20})
CFG = transformer_config(M, jnp.float32)
BF16 = transformer_config(M, jnp.bfloat16)
KIND = weights_latent.KIND
# float32 program against the float32 reference: sums in another order
TOL = 2e-4
# bfloat16 program (bf16 products, bf16 residual stream, bf16 cache)
# against the float32 reference on the same bf16-valued weights: logits
# of spread 1 move by a few hundredths, and by a few tenths where a
# routing choice flips (PERF.md 2 has the chip's numbers); the MEAN over
# the positions is what the benchmark's `correct` takes, for that reason.
# Read here over seeds 5, 7, 11: bf16 0.004 to 0.02; float8 0.13 to 0.3.
BF16_MEAN_GAP = 0.06


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def make_model(seed=5, m=M, share=()):
    """(params as the program holds them, the same layer by layer as the
    reference takes them): bfloat16 VALUES in float32, as the benchmark's
    weights are."""
    key = weights.seed_key(seed)
    params = f32(weights_latent.params(key, m, jnp.bfloat16, share))
    layers = [f32(weights_latent.layer(key, m, l, jnp.bfloat16, share))
              for l in range(m["num_hidden_layers"])]
    return params, layers


@pytest.fixture(scope="module")
def model():
    return make_model()


@functools.lru_cache(maxsize=None)
def _reference_fn(precision, broken):
    attn = {k: v for k, v in broken
            if k in ("mscale", "rope_score", "kv_norm")}
    rest = {k: v for k, v in broken if k not in attn}

    def forward(params, layers, tokens):
        x = params["embed"][tokens]
        for l, lp in enumerate(layers):
            x = ref.attention(lp, x, M, precision, **attn)
            x = ref.mlp(lp, x, M, l, precision, **rest)
        return ref.head(params["embed"], params["final_norm"]["scale"], x,
                        precision)

    return jax.jit(forward)


def reference(model, tokens, precision="f32", **broken):
    """Logits [T, V] of the plain reference; `broken` leaves a term out
    of every layer (`ref.attention` / `ref.mlp`'s switches)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference_fn(
            precision, tuple(sorted(broken.items())))(
                *model, jnp.asarray(tokens)))


SLOTS = 64


def decoded(params, prompt, new, cfg=CFG, slots=SLOTS):
    """Prefill (expanded) then `new - 1` decode steps (absorbed) through
    one cache: (tokens, the logits that chose each, the final cache)."""
    cast = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.compute_dtype), t)
    params = cast(params) if cfg.compute_dtype != jnp.float32 else params
    cache = init_decode_cache(cfg, 1, slots)
    lg, cache = transformer_prefill(params, cache,
                                    jnp.asarray(prompt[None]), cfg)
    toks, logits = [int(jnp.argmax(lg[0]))], [np.asarray(lg[0])]
    for _ in range(new - 1):
        lg, cache = transformer_decode_step(
            params, cache, jnp.asarray(toks[-1:], jnp.int32), cfg)
        toks.append(int(jnp.argmax(lg[0])))
        logits.append(np.asarray(lg[0]))
    return toks, np.stack(logits), cache


def mean_gap(want, toks):
    """The benchmark's number: how far the served token's logit lies
    below the reference's best, over the positions."""
    return float(np.mean(want.max(-1) - want[np.arange(len(toks)), toks]))


# -- the two forms, and the reference ---------------------------------------

@pytest.mark.parametrize("T0", [9, 130], ids=["dense-prompt", "flash-prompt"])
def test_prefill_then_decode_matches_the_reference(model, T0):
    """Prefill, then 16 decode steps through the cache, in float32: the
    expanded prompt and every absorbed step give the reference's logits
    (its one full expanded forward pass over prompt + tokens).  A prompt
    of 130 goes through the flash kernel, its heads padded to 128."""
    params, _ = model
    prompt = np.random.RandomState(T0).randint(0, V, size=T0)
    toks, got, cache = decoded(params, prompt, 17, slots=T0 + 32)
    want = reference(model, np.concatenate([prompt, toks[:-1]]))
    np.testing.assert_allclose(got, want[T0 - 1:], atol=TOL, rtol=0)
    # the cache: one head, the latent and the shared key in whole tiles
    sp = dict(CFG.attn_specs)[KIND]
    assert cache["k"][KIND].shape == (4, 1, 1, T0 + 32, sp.kv_rank)
    assert cache["v"][KIND].shape == (4, 1, 1, T0 + 32, 128)
    assert sp.key_lanes == 128 and sp.rope_dim == 8
    assert not np.asarray(cache["v"][KIND][..., sp.rope_dim:]).any()
    assert int(cache["pos"]) == T0 + 16


def test_absorbed_step_is_the_expanded_prefill(model):
    """The same position computed both ways: as the last token of an
    expanded prompt and as an absorbed step over that prompt's cache."""
    params, _ = model
    prompt = np.random.RandomState(1).randint(0, V, size=(2, 21))
    cache = init_decode_cache(CFG, 2, SLOTS)
    whole, _ = transformer_prefill(params, cache, jnp.asarray(prompt), CFG)
    _, cache = transformer_prefill(params, init_decode_cache(CFG, 2, SLOTS),
                                   jnp.asarray(prompt[:, :-1]), CFG)
    step, _ = transformer_decode_step(params, cache,
                                      jnp.asarray(prompt[:, -1]), CFG)
    np.testing.assert_allclose(np.asarray(step), np.asarray(whole),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("seed", [5, 7, 11])
def test_bf16_passes_and_float8_fails(seed):
    """The bfloat16 program stays under `BF16_MEAN_GAP` (the module text
    has the reason for the tolerance) and the reference computed in
    float8_e4m3, put in the program's place, does not: a program that ran
    a precision lower would be caught."""
    mdl = make_model(seed)
    prompt = np.random.RandomState(seed).randint(0, V, size=24)
    toks, _, _ = decoded(mdl[0], prompt, 17, cfg=BF16)
    seq = np.concatenate([prompt, toks[:-1]])
    want = reference(mdl, seq)[len(prompt) - 1:]
    assert mean_gap(want, toks) < BF16_MEAN_GAP
    fp8 = reference(mdl, seq, "fp8")[len(prompt) - 1:]
    assert mean_gap(want, fp8.argmax(-1)) > BF16_MEAN_GAP


@pytest.mark.parametrize("broken", [
    dict(mscale=False), dict(rope_score=False), dict(kv_norm=False),
    dict(groups=False), dict(scale=1.0), dict(shared=False),
    dict(bias_in_choice=False)],
    ids=lambda b: next(iter(b)))
def test_each_term_is_in_the_program(model, broken):
    """The reference with ONE term left out is no longer what the
    program computes: the m^2 on the softmax scale (the program with
    `(nope + rope)^-1/2` alone would fail here), the shared key's part of
    the score, the latent's norm, the group limit, the routed scale, the
    shared expert, the bias in the choice."""
    params, _ = model
    prompt = np.random.RandomState(3).randint(0, V, size=40)
    cache = init_decode_cache(CFG, 1, SLOTS)
    got, _ = transformer_prefill(params, cache, jnp.asarray(prompt[None]),
                                 CFG)
    want = reference(model, prompt)[-1]
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL, rtol=0)
    wrong = reference(model, prompt, **broken)[-1]
    assert np.abs(wrong - want).max() > 50 * TOL, broken


def test_softmax_scale_carries_m_squared():
    sp = dict(CFG.attn_specs)[KIND]
    m = 0.1 * np.log(8) + 1
    assert sp.softmax_scale == pytest.approx(24 ** -0.5 * m * m)
    assert sp.softmax_scale == pytest.approx(ref.softmax_scale(M))
    assert ref.softmax_scale(M, mscale=False) == pytest.approx(24 ** -0.5)
    # the published model: 192^-1/2 x 1.4158883^2
    big = dict(M, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=dict(M["rope_scaling"], factor=64))
    assert ref.softmax_scale(big) == pytest.approx(0.14468, abs=1e-5)


# -- routing in groups -------------------------------------------------------

def _route_cfg(**kw):
    return dataclasses.replace(CFG, **kw)


def test_route_with_groups_is_the_references(model):
    params, layers = model
    mp = layers[1]
    h = jax.random.normal(jax.random.PRNGKey(0), (50, 64), jnp.float32)
    idx, w = experts.route(mp["router"], h, CFG, mp["router_bias"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routing(mp["router"], mp["router_bias"], h, M))
    got = np.zeros_like(want)
    got[np.arange(50)[:, None], np.asarray(idx)] = np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # 4 experts, all within 2 of the 4 groups of 8
    assert all(len(set(row // 8)) <= 2 for row in np.asarray(idx))


def test_group_limit_drops_a_better_expert_and_the_bias_only_chooses():
    """Router logits set by hand (tokens are unit vectors, so a token's
    logits are a row of the router).  Token 0: groups 0 and 1 hold two
    strong experts each; group 2 holds ONE expert stronger than any of
    them and nothing else: its group scores worst of the three and is
    dropped, so the token's best expert overall is not chosen.  Token 1:
    a bias lifts a weak expert into the choice, and the weights are of
    the scores WITHOUT it."""
    E, D = 32, 64
    logits = np.full((2, E), -6.0, np.float32)
    logits[0, [0, 1]] = 2.0
    logits[0, [8, 9]] = 1.5
    logits[0, 16] = 3.0                     # alone in group 2
    logits[1, [0, 1, 8]] = 2.0
    logits[1, [9, 10]] = (0.5, 0.6)
    router = np.zeros((D, E), np.float32)
    router[:2] = logits
    h = jnp.eye(D, dtype=jnp.float32)[:2]
    bias = np.zeros(E, np.float32)
    bias[9] = 0.2                            # sigmoid(.5)+.2 > sigmoid(.6)
    idx, w = experts.route(jnp.asarray(router), h, CFG, jnp.asarray(bias))
    idx, w = np.asarray(idx), np.asarray(w)
    assert sorted(idx[0]) == [0, 1, 8, 9] and 16 not in idx[0]
    assert sorted(idx[1]) == [0, 1, 8, 9]
    s = 1 / (1 + np.exp(-logits[1, sorted(idx[1])]))
    np.testing.assert_allclose(np.sort(w[1]), np.sort(2.5 * s / s.sum()),
                               rtol=1e-5)
    # without groups the lone strong expert is token 0's first
    plain = _route_cfg(route_groups=0, route_groups_kept=0)
    idx0, _ = experts.route(jnp.asarray(router), h, plain, jnp.asarray(bias))
    assert 16 in np.asarray(idx0)[0]


@pytest.mark.parametrize("bias", [False, True])
def test_no_groups_is_bit_for_bit_what_it_was(model, bias):
    """`route_groups` 0: the values and the choice of the code as it
    stood before groups (copied here), bit for bit."""
    mp = model[1][1]
    cfg = _route_cfg(route_groups=0, route_groups_kept=0)
    h = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    b = mp["router_bias"] if bias else None

    def as_it_was(router, h, cfg, bias=None):
        scores = jax.nn.sigmoid(jnp.einsum(
            "nd,de->ne", h, router.astype(h.dtype),
            preferred_element_type=jnp.float32))
        if bias is None:
            top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
        else:
            _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                                   cfg.experts_per_token)
            top = jnp.take_along_axis(scores, idx, axis=-1)
        scaled = cfg.routed_scale * top
        den = jnp.sum(top, axis=-1, keepdims=True)
        if cfg.route_eps:
            den = den + cfg.route_eps
        return idx.astype(jnp.int32), scaled / den

    got, want = experts.route(mp["router"], h, cfg, b), \
        as_it_was(mp["router"], h, cfg, b)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_groups_are_checked():
    for kw in (dict(route_groups=5), dict(route_groups_kept=0),
               dict(route_groups_kept=5), dict(route_groups=32)):
        with pytest.raises(ValueError, match="route_groups"):
            dataclasses.replace(CFG, **kw)


def test_the_shares_add_up():
    """Four chips' shares of a 32-expert layer (8 experts each), the
    shared expert counted once, sum to the uncut reference layer; and
    `pairs_here` over the shares is every pair."""
    whole = dict(M, n_routed_experts=32, experts_held=[0, 32])
    _, layers = make_model(m=whole)
    lp = layers[2]
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mlp(lp, h, whole, 2, shared=True)) \
            - np.asarray(h)
        shared = want - (np.asarray(ref.mlp(lp, h, whole, 2, shared=False))
                         - np.asarray(h))
    # what the reference norms, the program is handed normed
    hn = ref.rmsnorm(lp["ln2"]["scale"], h)
    total, pairs = np.zeros_like(want), 0
    for lo in range(0, 32, 8):
        cfg = dataclasses.replace(CFG, experts_held=(lo, lo + 8))
        stack = {n: w[None, lo:lo + 8] for n, w in lp["experts"].items()}
        out, counts = experts.expert_layer(lp, stack, 0, hn, cfg)
        total += np.asarray(out) - shared       # every chip adds it alike
        assert 0 <= int(counts[0]) <= 8
        pairs += int(counts[2])
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)
    assert pairs == 24 * 4                       # k pairs a token


def test_a_long_pass_goes_in_passes(model, monkeypatch):
    """More tokens than `_pass_tokens`: the same result and the same
    counts, in passes one after another."""
    lp = model[1][1]
    stack = {n: w[None] for n, w in lp["experts"].items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (50, 64), jnp.float32)
    live = jnp.arange(50) % 7 != 0
    want, cw = experts.expert_layer(lp, stack, 0, h, CFG, live)
    monkeypatch.setattr(experts, "_PASS_BYTES", 4 * 64 * 4 * 16)  # 16 tokens
    got, cg = experts.expert_layer(lp, stack, 0, h, CFG, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert int(cg[0]) == int(cw[0]) and int(cg[2]) == int(cw[2])
    assert int(cg[1]) == int(cw[1])


# -- the kernel --------------------------------------------------------------

@pytest.mark.parametrize("positions", [
    [0, 700, 1279, 3], [511, 512, 0, 1023]], ids=["ragged", "block-edges"])
def test_kernel_against_the_einsum(positions):
    """ops/decode_attention.py over a latent (interpreted here): an idle
    row, a row that ends inside a block, rows at a block's edge, a full
    ring, against the contractions over every slot."""
    B, H, R, dr, S, L = 4, 8, 128, 64, 1280, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    ck = jax.random.normal(ks[0], (L, B, 1, S, R), jnp.float32)
    cv = jnp.pad(jax.random.normal(ks[1], (L, B, 1, S, dr), jnp.float32),
                 ((0, 0),) * 4 + ((0, 128 - dr),))
    qc = jax.random.normal(ks[2], (B, H, R), jnp.float32)
    qr = jax.random.normal(ks[3], (B, H, dr), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    assert decode_attention.reads_live(S)
    q = jnp.concatenate([qc, jnp.pad(qr, ((0, 0), (0, 0), (0, 128 - dr)))],
                        -1)[:, None]
    got = decode_attention.decode_attention(
        q, ck, cv, 1, pos, scale=0.07, latent=True)[:, 0]
    want = decode._latent_attend_view(qc, qr, ck, cv, 1, pos, 0.07)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    # an idle row's output is the slot it "wrote": the wrapper's select
    idle = positions.index(0)
    np.testing.assert_array_equal(
        np.asarray(got[idle]),
        np.broadcast_to(np.asarray(ck[1, idle, 0, 0]), (H, R)))


def test_a_long_view_is_read_by_the_kernel(model, monkeypatch):
    """A view of two blocks or more: the absorbed step goes through the
    kernel (counted) and gives what the einsum over every slot gives."""
    params, _ = model
    calls = []
    real = decode_attention.decode_attention
    monkeypatch.setattr(
        decode_attention, "decode_attention",
        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    prompt = np.random.RandomState(2).randint(0, V, size=(2, 12))
    out = {}
    for slots in (64, 1024):
        cache = init_decode_cache(CFG, 2, slots)
        _, cache = transformer_prefill(params, cache, jnp.asarray(prompt),
                                       CFG)
        cache["pos"] = jnp.asarray([12, 0], jnp.int32)    # row 1 idle
        out[slots], _ = transformer_decode_step(
            params, cache, jnp.asarray([7, 0], jnp.int32), CFG)
    assert len(calls) == 4 and all(kw["latent"] for kw in calls)
    assert calls[0]["scale"] == dict(CFG.attn_specs)[KIND].softmax_scale
    np.testing.assert_allclose(np.asarray(out[1024][0]),
                               np.asarray(out[64][0]), atol=TOL, rtol=0)


# -- served ------------------------------------------------------------------

def test_pooled_decode_is_bitwise_contiguous_decode(model):
    """Over leaves of unequal width: a step over the view gathered from
    the pages consumes the bytes a contiguous cache holds, so its logits
    are the same bits, step after step, and what is written through comes
    back in the next gather."""
    params, _ = model
    B, T0, steps, ring = 2, 6, 5, 16
    toks = np.random.RandomState(4).randint(0, V, size=(B, T0))
    pool = KindKVPool(CFG, total_pages=2 * ring // 4, page_tokens=4,
                      rows=B, view_pages=ring // 4)
    assert pool.k.shape[-1] == 32 and pool.v.shape[-1] == 128
    rows = []
    for b in range(B):
        lg = pool.board(b, b, ring, params, toks[b],
                        lambda p, c, t: transformer_prefill(p, c, t, CFG))
        scratch = init_decode_cache(CFG, 1, ring)
        _, scratch = transformer_prefill(params, scratch,
                                         jnp.asarray(toks[b:b + 1]), CFG)
        rows.append((np.asarray(lg), scratch))
    cat = lambda n: jnp.concatenate([r[1][n][KIND] for r in rows], axis=1)
    cont = {"k": {KIND: cat("k")}, "v": {KIND: cat("v")}}
    pos = np.full(B, T0)
    tok = jnp.asarray(np.concatenate([r[0] for r in rows]).argmax(-1),
                      jnp.int32)
    for _ in range(steps):
        pool.refresh()
        np.testing.assert_array_equal(np.asarray(pool.view[0]),
                                      np.asarray(cont["k"][KIND]))
        np.testing.assert_array_equal(np.asarray(pool.view[1]),
                                      np.asarray(cont["v"][KIND]))
        lg_p, cache = transformer_decode_step(params, pool.lend(pos), tok,
                                              CFG)
        pool.take_back(cache)
        pool.write_through(list(range(B)), pos)
        lg_c, cont = transformer_decode_step(
            params, {**cont, "pos": jnp.asarray(pos, jnp.int32)}, tok, CFG)
        np.testing.assert_array_equal(np.asarray(lg_p), np.asarray(lg_c))
        tok, pos = jnp.argmax(lg_p, -1).astype(jnp.int32), pos + 1
        pool.view = None                     # gather the pages afresh


def test_server_end_to_end(model):
    """Through `submit`, the scheduler, `make_cache` and the step
    programs the other models use: the tokens `transformer_generate`
    gives, the cache's gauge under its own label, the pairs counted."""
    params, _ = model
    srv = InferenceServer(params, CFG, max_seq_tokens=40, max_batch=3,
                          page_tokens=4)
    assert isinstance(srv.pool, KindKVPool) and srv.pool.latent
    assert not isinstance(srv.pool, WindowedKVPool)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, V, size=n) for n in (5, 11, 8, 14)]
    ids = [srv.submit(p.tolist(), 9) for p in prompts]
    done = {s.req.req_id: s.generated for s in srv.run()}
    for rid, p in zip(ids, prompts):
        want, _ = transformer_generate(params, CFG, jnp.asarray(p[None]), 9)
        assert done[rid] == np.asarray(want)[0].tolist()
    sparse = 3
    assert srv.moe_layer_steps == sparse * srv.device_steps
    # one step kept in flight: all but the first of the three requests
    # that board together and the first of the fourth, which waits for them
    assert srv.steps_ahead == srv.device_steps - 2
    routed = 4 * sparse * srv.occupancy_sum * srv.max_batch
    assert 0 < srv.pairs_here_sum < routed
    assert srv.experts_hit_sum <= 8 * srv.moe_layer_steps
    srv.flush_metrics()
    held = srv.pool.page_bytes + sum(a.nbytes for a in srv.pool.view)
    assert met.serve_cache_bytes.labels("latent").get() == held > 0
    # a page: one head of latent and of shared key, 4 tokens
    assert srv.pool.k.shape[1:] == (srv.pool.total_pages, 1, 4, 32)
    assert srv.pool.v.shape[1:] == (srv.pool.total_pages, 1, 4, 128)


def test_a_pattern_without_windows_gets_pages_and_no_rings():
    """`make_cache`: one kind that sees the whole context, latent or not,
    is `KindKVPool`; a window kind still brings `WindowedKVPool`."""
    from horovod_tpu.models.transformer import AttnSpec, TransformerConfig
    shape = dict(rows=2, view_pages=4, page_tokens=4, pool_pages=8,
                 quantize=None, speculative=False, rows_held=lambda: 0)
    plain = TransformerConfig(
        vocab_size=64, d_model=32, d_head=8, d_ff=64, n_layers=2,
        n_kv_heads=2, compute_dtype=jnp.float32, layer_attn=("full",) * 2,
        layer_mlp=("dense",) * 2, attn_specs=(("full", AttnSpec(4)),))
    pool = make_cache(plain, **shape)
    assert type(pool) is KindKVPool and not pool.latent
    assert not hasattr(pool, "rings")
    windowed = dataclasses.replace(
        plain, layer_attn=("full", "win"),
        attn_specs=(("full", AttnSpec(4)), ("win", AttnSpec(4, 4))))
    assert type(make_cache(windowed, **shape)) is WindowedKVPool
    assert type(make_cache(CFG, **shape)) is KindKVPool


# -- what refuses, by name ---------------------------------------------------

def test_refusals_name_the_kind(model):
    params, _ = model
    prompt = jnp.zeros((1, 4), jnp.int32)
    named = "latent kind of attention layer"
    with pytest.raises(InvalidRequestError, match=named):
        init_decode_cache(CFG, 1, 16, quantize="int8")
    with pytest.raises(InvalidRequestError, match=named):
        InferenceServer(params, CFG, max_seq_tokens=16, max_batch=2,
                        page_tokens=4, quantize="int8")
    with pytest.raises(InvalidRequestError, match=named):
        InferenceServer(params, CFG, max_seq_tokens=16, max_batch=2,
                        page_tokens=4, draft_params=params, draft_cfg=CFG)
    with pytest.raises(InvalidRequestError, match=named):
        transformer_speculative_generate(params, CFG, params, CFG, prompt, 4)
    with pytest.raises(InvalidRequestError, match=named):
        transformer_extend(params, init_decode_cache(CFG, 1, 16), prompt,
                           CFG)
    with pytest.raises(InvalidRequestError, match=named):
        transformer_beam_search(params, CFG, prompt, 4, beam_width=2)
    with pytest.raises(InvalidRequestError, match=named):
        make_decode_step(None, CFG)          # dp/tp sharding of the cache
    # a chunk handed to the layer itself, and a tp axis
    kc = CFG.kind_cfg(KIND)
    lp = jax.tree_util.tree_map(lambda p: p[0], params["attn"][KIND])
    cache = init_decode_cache(CFG, 1, 16)
    args = (lp, cache["k"][KIND], cache["v"][KIND], 0)
    with pytest.raises(InvalidRequestError, match="one token a row"):
        decode._latent_decode_layer(*args, jnp.zeros((1, 2, 64)), 0, kc)
    for layer, x in ((decode._latent_decode_layer, jnp.zeros((1, 1, 64))),
                     (decode._latent_prefill_layer, jnp.zeros((1, 4, 64)))):
        with pytest.raises(InvalidRequestError, match="tensor parallelism"):
            kw = dict(pos=0) if layer is decode._latent_decode_layer else {}
            layer(*args, x, cfg=kc, tp_axis="tp", **kw)


def test_training_refuses_the_kind():
    import optax
    from horovod_tpu.parallel import create_hybrid_mesh
    mesh = create_hybrid_mesh(devices=jax.devices()[:1], dp=1)
    with pytest.raises(HorovodTpuError, match="latent kind of attention"):
        make_train_step(mesh, CFG, optax.sgd(0.1))


def test_init_lays_the_published_leaves():
    p = transformer_init(jax.random.PRNGKey(0), CFG)
    shapes = {n: a.shape for n, a in p["attn"][KIND].items()
              if not isinstance(a, dict)}
    assert shapes == {"wq_a": (4, 64, 48), "wq_b": (4, 48, 4, 24),
                      "wkv_a": (4, 64, 40), "wkv_b": (4, 32, 4, 40),
                      "wo": (4, 4, 24, 64)}
    ours = make_model()[0]
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(p)
    assert p["mlp"]["experts"]["router"].shape == (3, 64, 32)
    assert p["mlp"]["experts"]["experts"]["wi"].shape == (3, 8, 64, 32)
