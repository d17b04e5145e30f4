"""A model with a layer PATTERN (`TransformerConfig.layer_attn`: full and
sliding attention layers that differ in heads, rotary form and cache; a
gate a head; top-k routed experts beside a shared one, models/experts.py)
against the plain reference (benchmark/reference/pattern_moe.py: float32
"highest", no cache, no sorting, every expert on every token), at a small
size on the CPU with seeded random weights.

Tolerance of every comparison of logits: 1e-4 absolute on logits of
magnitude 3.  Program and reference are both float32 here and differ in
the ORDER of their sums only (a ring and a grouped product over sorted
pairs against one pass over all keys and all experts): the widest gap
seen is 1e-5, and a bfloat16 product lands at 1e-2.  Routing is discrete,
so a case is only sound while no token's last chosen and first left-out
router logit lie closer than float32's rounding: each asserts its least
margin (seen: 9e-4 and over).
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import weights, weights_pattern
from benchmark.reference import pattern_moe as ref
from benchmark.runners.pattern_serve import transformer_config
from horovod_tpu.common.exceptions import (HorovodTpuError,
                                           InvalidRequestError)
from horovod_tpu.models import (TransformerConfig, init_decode_cache,
                                make_decode_step, make_train_step,
                                transformer_beam_search,
                                transformer_decode_step, transformer_extend,
                                transformer_generate, transformer_init,
                                transformer_prefill, transformer_ref_apply,
                                transformer_speculative_generate)
from horovod_tpu.models import experts
from horovod_tpu.models.decode import _rotate
from horovod_tpu.models.transformer import AttnSpec, Rotary
from horovod_tpu.serve import InferenceServer
from horovod_tpu.serve.pool import (PagedKVPool, PoolExhaustedError,
                                    WindowedKVPool)

TOL = 1e-4
V, WINDOW = 320, 8

# Laguna-XS.2's shape at a size a test can hold: layer 0 full attention
# with a dense MLP, then a period of three sliding layers (window 8, 8
# heads, plain rotary on the whole head) and a full one (6 heads, YaRN
# over an original context of 16 on half the head), all four with 4 of 16
# routed experts beside a shared one.
M = dict(
    vocab_size=V, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, gating=True,
    sliding_window=WINDOW, moe_routed_scaling_factor=2.5,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[6, 8, 8, 8, 6])
CFG = transformer_config(M, jnp.float32)


@pytest.fixture(scope="module")
def model():
    """(params as the program holds them, the same layer by layer as the
    reference takes them): bfloat16 VALUES in float32, as the benchmark's
    weights are."""
    key = weights.seed_key(5)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    params = f32(weights_pattern.params(key, M, jnp.bfloat16))
    layers = [f32(weights_pattern.layer(key, M, l, jnp.bfloat16))
              for l in range(M["num_hidden_layers"])]
    return params, layers


def reference(model, tokens):
    """(logits [T, V], least routing margin) of the plain reference."""
    params, layers = model
    T = len(tokens)
    pad = -T % ref.Q_BLOCK if T > ref.Q_BLOCK else 0
    with jax.default_matmul_precision("highest"):
        logits, margins = ref.forward(
            layers, params["embed"], params["final_norm"]["scale"],
            jnp.asarray(np.pad(tokens, (0, pad))), M)
    return np.asarray(logits)[:T], float(np.asarray(margins)[:, :T].min())


SLOTS = 64          # one cache shape, so one step program, for every case
STEP = jax.jit(lambda p, c, t: transformer_decode_step(p, c, t, CFG))
PREFILL = jax.jit(lambda p, c, t: transformer_prefill(p, c, t, CFG))


def decoded(params, prompt, new, slots=SLOTS):
    """Prefill then `new - 1` decode steps through the cache: (tokens,
    the logits that chose each, the final cache)."""
    cache = init_decode_cache(CFG, 1, slots)
    lg, cache = PREFILL(params, cache, jnp.asarray(prompt[None]))
    toks, logits = [int(jnp.argmax(lg[0]))], [np.asarray(lg[0])]
    for _ in range(new - 1):
        lg, cache = STEP(params, cache, jnp.asarray(toks[-1:], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        logits.append(np.asarray(lg[0]))
    return toks, np.stack(logits), cache


@pytest.mark.parametrize("T0,new", [(5, 12), (20, 14), (130, 4)],
                         ids=["ring-fills-then-wraps", "prompt-past-window",
                              "flash-prefill"])
def test_prefill_then_decode_matches_the_reference(model, T0, new):
    """Through the cache (pages' worth of slots for the full layers, a
    ring of 8 for the sliding ones) against the reference's one pass over
    prompt and tokens; every case decodes past the point where a ring
    wraps.  A prompt of 130 goes through the flash kernel, padded."""
    params, _ = model
    prompt = np.random.RandomState(T0).randint(0, V, size=T0)
    slots = SLOTS if T0 < SLOTS else T0 + new
    toks, got, cache = decoded(params, prompt, new, slots)
    want, margin = reference(model, np.concatenate([prompt, toks[:-1]]))
    assert margin > 1e-4
    np.testing.assert_allclose(got, want[T0 - 1:], atol=TOL, rtol=0)
    assert cache["k"]["sliding_attention"].shape[3] == WINDOW
    assert cache["k"]["full_attention"].shape[3] == slots
    assert int(cache["pos"]) == T0 + new - 1
    # a step of one row: each sparse layer's token chose 4 experts, one
    # token each, all 4 pairs here
    np.testing.assert_array_equal(np.asarray(cache["routed"]),
                                  [[4, 1, 4]] * 4)


def test_generate_is_the_same_walk(model):
    params, _ = model
    prompt = np.random.RandomState(3).randint(0, V, size=(2, 20))
    toks, _ = transformer_generate(params, CFG, jnp.asarray(prompt), 8)
    for b in range(2):
        assert decoded(params, prompt[b], 8)[0] == \
            np.asarray(toks)[b].tolist()


def test_served_rows_at_mixed_depths(model):
    """`InferenceServer`: two rows for four requests of prompts of 5 and
    20 tokens, so rows sit before, at and past their rings' wrap in one
    step, rows are reused, and a row idles at the end.  Token for token
    what the cache walk gives one request alone, and the logits behind
    the last decision within the reference's tolerance."""
    params, _ = model
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, V, size=n) for n in (5, 20, 20, 5)]
    srv = InferenceServer(params, CFG, max_seq_tokens=32, max_batch=2,
                          page_tokens=4)
    assert isinstance(srv.pool, WindowedKVPool)
    rids = [srv.submit(p, 10) for p in prompts]
    by_id, last = {}, {}
    while not srv.sched.drained():
        for seq in srv.step():
            by_id[seq.req.req_id] = list(seq.generated)
        for row, seq in srv.sched.active.items():
            last[seq.req.req_id] = (len(seq.generated),
                                    srv.last_logits[row].copy())
    assert srv.moe_layer_steps == 4 * srv.device_steps
    # a step was in flight throughout, reading `last_logits` lands none:
    # every step but the first of each wave of two requests was ahead
    assert srv.steps_ahead == srv.device_steps - 2
    for rid, p in zip(rids, prompts):
        assert by_id[rid] == decoded(params, p, 10)[0]
        n, logits = last[rid]            # chose token n of this request
        want, margin = reference(
            model, np.concatenate([p, by_id[rid][:n]]))
        assert margin > 1e-4
        np.testing.assert_allclose(logits, want[-1], atol=TOL, rtol=0)
    assert srv.pool.utilization() == 0.0


def test_idle_rows_are_routed_nowhere(model):
    """One request in a batch of four: the step's counts are one token's
    (4 experts a sparse layer, the fullest took 1), not four rows'."""
    params, _ = model
    srv = InferenceServer(params, CFG, max_seq_tokens=24, max_batch=4,
                          page_tokens=4)
    srv.submit(np.arange(5), 5)
    srv.run()
    assert srv.device_steps == 4 and srv.moe_layer_steps == 16
    assert srv.experts_hit_sum == 4 * 16
    assert srv.expert_load_max_sum == 16


def _expert_parts(model, held):
    """The routed part of sparse layer 1 for 24 tokens, computed by a
    holder of experts `held` alone (no shared expert)."""
    params, _ = model
    mp = params["mlp"]["experts"]
    lo, hi = held
    cfg = dataclasses.replace(CFG, experts_held=held)
    stack = {n: w[:, lo:hi] for n, w in mp["experts"].items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
    out, counts = experts.expert_layer(
        {"router": mp["router"][1]}, stack, 1, x, cfg)
    return h, x, np.asarray(out), np.asarray(counts)


def test_shares_add_up_to_the_uncut_layer(model):
    """The guide's shares test: what the holders of experts 0..3, 4..11
    and 12..15 each compute of a layer's routed part, with the shared
    expert, which every holder computes alike, counted ONCE, is what the
    uncut reference gives for the whole layer."""
    params, layers = model
    h, x, total, whole = _expert_parts(model, (0, 16))
    parts = [_expert_parts(model, r)[2:]
             for r in ((0, 4), (4, 12), (12, 16))]
    np.testing.assert_allclose(sum(p[0] for p in parts), total, atol=1e-5)
    # layer 2 is sparse layer 1; the reference norms h and adds it back
    lp = layers[2]
    with jax.default_matmul_precision("highest"):
        want, margin = ref.mlp(lp, h, M, 2)
        routed_only, _ = ref.mlp(lp, h, M, 2, shared=False)
        shared = np.asarray(want - routed_only)
        np.testing.assert_allclose(
            shared, np.asarray(experts.swiglu(
                jax.tree_util.tree_map(
                    lambda a: a[1], params["mlp"]["experts"]["shared"]),
                x, jnp.float32)), atol=1e-5)
    assert float(margin.min()) > 1e-4
    np.testing.assert_allclose(total + shared, np.asarray(want - h),
                               atol=1e-5)
    # a holder counts its own experts only
    assert sum(p[1][0] for p in parts) == whole[0]


def test_top_k_weights_sum_to_the_routed_scale(model):
    params, _ = model
    h = jax.random.normal(jax.random.PRNGKey(2), (50, 64), jnp.float32)
    idx, w = experts.route(params["mlp"]["experts"]["router"][0], h, CFG)
    assert idx.shape == w.shape == (50, 4)
    assert all(len(set(row)) == 4 for row in np.asarray(idx).tolist())
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    assert float(w.min()) > 0


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
@pytest.mark.parametrize("per_row", [False, True], ids=["shared", "rows"])
def test_rotary_forms_beyond_the_original_context(kind, per_row):
    """YaRN on half the head and the plain form on all of it, against the
    reference, at positions up to 8 times the original 16 that YaRN's
    frequencies were fitted to."""
    rp = M["rope_parameters"][kind]
    x = jax.random.normal(jax.random.PRNGKey(7), (128, 3, 16), jnp.float32)
    want = np.asarray(ref.rotary(x, rp))
    kc = CFG.kind_cfg(kind)
    if per_row:      # two rows at their own depths, 2 positions each
        pos = jnp.asarray([[5, 6], [120, 121]])
        got = _rotate(x[jnp.asarray([[5, 6], [120, 121]])], pos, kc)
        want = want[np.asarray([[5, 6], [120, 121]])]
    else:
        got = _rotate(x[None], jnp.arange(128), kc)[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    if kind == "full_attention":          # the unrotated half passes
        np.testing.assert_array_equal(
            np.asarray(got)[..., 8:],
            np.asarray(x[jnp.asarray([[5, 6], [120, 121]])]
                       if per_row else x)[..., 8:])
        f, n = kc.rotary.tables(16)
        np.testing.assert_allclose(f, ref.yarn_freqs(rp, 16), rtol=1e-12)
        assert n == 4 and f[-1] == pytest.approx(
            500000 ** (-3 / 4) / 8)      # the slowest pair: interpolated


def test_admission_counts_the_full_layers_pages_only(model):
    """Pages are the full layers': a request takes ceil(tokens / page) of
    them whatever the sliding layers hold, the rings are there from the
    start and never grow, and a full pool holds a request back."""
    params, _ = model
    srv = InferenceServer(params, CFG, max_seq_tokens=24, max_batch=2,
                          page_tokens=4, pool_pages=6)
    pool = srv.pool
    assert pool.pool.k.shape[0] == 2          # the two full layers
    assert pool.pages_needed(24) == 6 and pool.total_pages == 6
    rings = pool.ring_bytes
    assert rings == 2 * 3 * 2 * 2 * WINDOW * 16 * 4   # k, v: 3 layers x 2 rows
    srv.submit(np.arange(20) % 7, 4)         # 24 tokens: all six pages
    srv.step()
    srv.submit(np.arange(5), 4)              # a free row, and no free page
    srv.step()
    assert pool.pages_free() == 0 and srv.sched.queue_depth() == 1
    assert not pool.can_board(9) and pool.ring_bytes == rings
    with pytest.raises(PoolExhaustedError):
        pool.pool.alloc(99, 1)
    assert len(srv.run()) == 2
    assert pool.pages_free() == 6 and pool.installs == 2


def test_boarding_writes_the_prompts_last_window_into_the_ring(model):
    """After boarding a prompt of 21 tokens, slot p % 8 of the row's ring
    holds the key of position p for the last 8 positions, as a cache that
    decoded them one by one holds them (but for slot 21 % 8, which the
    admitting step's own decode has written since)."""
    params, _ = model
    prompt = np.random.RandomState(4).randint(0, V, size=21)
    srv = InferenceServer(params, CFG, max_seq_tokens=32, max_batch=2,
                          page_tokens=4)
    srv.submit(np.arange(5), 2)
    srv.submit(prompt, 2)
    srv.step()
    row = next(r for r, s in srv.sched.active.items()
               if len(s.req.prompt) == 21)
    ring = np.asarray(srv.pool.rings[0]["sliding_attention"])[:, row]
    cache = init_decode_cache(CFG, 1, SLOTS)
    _, cache = PREFILL(params, cache, jnp.asarray(prompt[None, :5]))
    for t in prompt[5:]:
        _, cache = STEP(params, cache, jnp.asarray([t], jnp.int32))
    kept = [s for s in range(WINDOW) if s != 21 % WINDOW]
    np.testing.assert_allclose(
        ring[:, :, kept],
        np.asarray(cache["k"]["sliding_attention"])[:, 0][:, :, kept],
        atol=1e-5)


# make_train_step trains a layer pattern since PR 37 (models/pattern.py,
# tests/test_conv_moe_train.py); what it still refuses of THIS model is
# its windows, its gate a head and its shared expert, and every mesh axis
# but dp.
_MESH = lambda **axes: types.SimpleNamespace(shape=axes)
_NO_WINDOW = dataclasses.replace(CFG, attn_specs=tuple(
    (t, dataclasses.replace(s, window=0)) for t, s in CFG.attn_specs))
REFUSALS = {
    "make_train_step, a window": lambda p: make_train_step(
        _MESH(dp=1), CFG, None),
    "make_train_step, a gate a head": lambda p: make_train_step(
        _MESH(dp=1), _NO_WINDOW, None),
    "make_train_step, a shared expert": lambda p: make_train_step(
        _MESH(dp=1), dataclasses.replace(_NO_WINDOW, attn_gate=False),
        None),
    "make_train_step, a tp axis": lambda p: make_train_step(
        _MESH(dp=1, tp=2), CFG, None),
    "training forward": lambda p: transformer_ref_apply(
        p, jnp.zeros((1, 4), jnp.int32), CFG),
    "quantized cache": lambda p: init_decode_cache(CFG, 1, 8, "int8"),
    "transformer_extend": lambda p: transformer_extend(
        p, init_decode_cache(CFG, 1, 8), jnp.zeros((1, 2), jnp.int32), CFG),
    "speculative decoding": lambda p: transformer_speculative_generate(
        p, CFG, p, CFG, jnp.zeros((1, 4), jnp.int32), 4),
    "beam search": lambda p: transformer_beam_search(
        p, CFG, jnp.zeros((1, 4), jnp.int32), 4),
    "make_decode_step": lambda p: make_decode_step(None, CFG),
    "served quantized": lambda p: InferenceServer(
        p, CFG, max_seq_tokens=16, max_batch=2, page_tokens=4,
        quantize="int8"),
    "served with a draft": lambda p: InferenceServer(
        p, CFG, max_seq_tokens=16, max_batch=2, page_tokens=4,
        draft_params=p, draft_cfg=CFG),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_by_name(model, what):
    """What is not built for a patterned model raises, and says that the
    layer pattern is why."""
    with pytest.raises((HorovodTpuError, InvalidRequestError),
                       match="layer pattern"):
        REFUSALS[what](model[0])


BAD = {
    "a kind of no spec": dict(layer_attn=("full", "other")),
    "too few names": dict(layer_mlp=("dense",)),
    "heads over kv heads": dict(attn_specs=(("full", AttnSpec(5)),)),
    "half a pair": dict(attn_specs=(
        ("full", AttnSpec(4, rotary=Rotary(share=0.4))),)),
    "an mlp of no kind": dict(layer_mlp=("dense", "sparse")),
    "experts out of range": dict(experts_held=(4, 20)),
    "retention": dict(attn_kind="retention"),
    "moe_every": dict(moe_every=2),
    "specs without a pattern": dict(layer_attn=(), layer_mlp=()),
    "prompt_attention": dict(prompt_attention="banded"),
}


@pytest.mark.parametrize("what", sorted(BAD))
def test_config_refuses(what):
    good = dict(vocab_size=32, d_model=16, n_heads=4, d_head=8, d_ff=32,
                n_layers=2, n_kv_heads=2, layer_attn=("full", "full"),
                layer_mlp=("dense", "experts"),
                attn_specs=(("full", AttnSpec(4)),), n_experts=16,
                experts_per_token=2, expert_ff=8)
    TransformerConfig(**good)
    with pytest.raises(ValueError):
        TransformerConfig(**{**good, **BAD[what]})


def test_init_gives_the_tree_the_walk_takes():
    """`transformer_init` of a patterned configuration: leaves stacked by
    kind of layer, the experts those held here; it generates."""
    cfg = dataclasses.replace(CFG, experts_held=(4, 12))
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    assert params["attn"]["sliding_attention"]["wq"].shape == (3, 64, 8, 16)
    assert params["attn"]["full_attention"]["w_gate"].shape == (2, 64, 6)
    assert params["mlp"]["dense"]["wi"].shape == (1, 64, 128)
    assert params["mlp"]["experts"]["experts"]["wd"].shape == (4, 8, 32, 64)
    assert params["mlp"]["experts"]["router"].shape == (4, 64, 16)
    toks, cache = transformer_generate(
        params, cfg, jnp.zeros((2, 5), jnp.int32), 3)
    assert toks.shape == (2, 3)
    assert np.asarray(cache["routed"])[:, 0].max() <= 8


def test_uniform_models_keep_their_programs():
    """A configuration without a pattern takes no new branch: its decode
    step and prefill trace to the same text whether or not this module's
    new fields exist (the fields at their defaults add no operation: the
    rotary form is `_rope` itself, no gate, no flash)."""
    from horovod_tpu.models.decode import _rope_rows
    from horovod_tpu.models.transformer import _rope
    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=4, d_head=8,
                            d_ff=32, n_layers=2, n_kv_heads=2)
    x = jnp.ones((2, 3, 4, 8))
    np.testing.assert_array_equal(
        _rotate(x, jnp.arange(3), cfg), _rope(x, jnp.arange(3), 10000.0))
    pos = jnp.asarray([[0, 1, 2], [5, 6, 7]])
    np.testing.assert_array_equal(
        _rotate(x, pos, cfg), _rope_rows(x, pos, 10000.0))
    assert not cfg.patterned and cfg.rotary is None
    assert isinstance(InferenceServer(
        transformer_init(jax.random.PRNGKey(0), cfg), cfg,
        max_seq_tokens=8, max_batch=2, page_tokens=4).pool, PagedKVPool)
