"""A patterned model TRAINED through `make_train_step`
(models/pattern.py): gated short-convolution and softmax layers in one
pattern, top-k routed experts chosen with a bias, each layer recomputed,
against the plain reference (benchmark/reference/conv_moe.py: float32
"highest", the convolution as shifted products, masked softmax, a loop
over the experts held) at a small size on the CPU with seeded weights.

Tolerances: program and reference are both float32 here and differ in
the ORDER of their sums only (a grouped product over sorted pairs, the
flash kernel's blocks, against one pass over all keys and every expert on
every token): logits agree to 1e-4 absolute on magnitude 2 (seen 4e-6),
losses to 1e-5 relative (seen 2e-7), a leaf's gradient to 2e-3 of the
leaf's norm (seen 5e-5; a bfloat16 product lands at 1e-2).  Routing is
discrete: a case is sound while no token's last chosen and first left-out
biased score lie closer than float32's rounding (asserted: 1e-5).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.lib import weights
from benchmark.lib import weights_conv_moe as W
from benchmark.reference import conv_moe as ref
from benchmark.runners.conv_moe_train import transformer_config
from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import (TransformerConfig, init_decode_cache,
                                make_train_step, transformer_generate,
                                transformer_init)
from horovod_tpu.models import experts, pattern
from horovod_tpu.models.transformer import AttnSpec, ConvSpec
from horovod_tpu.parallel import create_hybrid_mesh
from horovod_tpu.serve import InferenceServer

V, T = 96, 128

# LFM2-8B-A1B's shape at a size a test can hold, all three combinations of
# mixer and FFN: a convolution layer with the dense MLP, then an attention
# layer (q/k norm; 128 tokens, so the flash kernel) and a convolution
# layer with 4 of 16 routed experts a token, chosen with the bias.
M = dict(
    vocab_size=V, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_dense_layers=1, first_source_layer=1,
    layer_types=["conv", "conv", "full_attention", "conv"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    conv_L_cache=3, rope_theta=1000000, num_experts=16, router_width=16,
    num_experts_per_tok=4, moe_intermediate_size=32,
    routed_scaling_factor=1, use_expert_bias=True,
    assumed={"router_bias_std": 0.02},
    train={"optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                         "eps": 1e-8, "weight_decay": 1e-4}})
CFG = transformer_config(M, jnp.float32)
SHARES = [(0, 4), (4, 8), (8, 12), (12, 16)]


def make_params(m, seed=5):
    return jax.jit(lambda k: W.params(k, m, jnp.float32))(
        weights.seed_key(seed))


@pytest.fixture(scope="module")
def params():
    return make_params(M)


@pytest.fixture(scope="module")
def batch():
    toks = weights.lm_tokens(weights.seed_key(5), 0, 2, T + 1, V)
    return toks[:, :-1], toks[:, 1:]


def program_loss(p, tokens, targets, cfg=CFG):
    return pattern.pattern_loss_shard(p, tokens, targets, cfg, False)[0]


def program_logits(p, tokens, cfg=CFG):
    """float32 logits [B, T, V] (the loss never holds them whole)."""
    from horovod_tpu.models.transformer import _rmsnorm
    x, _ = pattern.pattern_forward(p, tokens, cfg)
    x = _rmsnorm(p["final_norm"]["scale"], x)
    return jnp.einsum("btd,vd->btv", x.astype(cfg.compute_dtype),
                      p["embed"].astype(cfg.compute_dtype),
                      preferred_element_type=jnp.float32)


def reference_loss(p, tokens, targets, m=M):
    with jax.default_matmul_precision("highest"):
        return ref.loss(p, tokens, targets, m)


def rel(got, want):
    """Norm of the difference over the reference's norm, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b)
                           / max(float(jnp.linalg.norm(b)), 1e-6)),
        got, want)


def test_logits_match_the_reference(params, batch):
    tokens = batch[0]
    got = jax.jit(program_logits)(params, tokens)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.logits(params, tokens[b], M)
                          for b in range(tokens.shape[0])])
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_loss_and_every_leafs_gradient_match_the_reference(params, batch):
    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params, *batch)
    want, want_grads = jax.jit(jax.value_and_grad(reference_loss))(
        params, *batch)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    worst = rel(grads, want_grads)
    leaves = jax.tree_util.tree_leaves_with_path(worst)
    assert len(leaves) == 23
    for path, gap in leaves:
        assert gap <= 2e-3, (jax.tree_util.keystr(path), gap)
    # the bias takes part in the choice only: no gradient, either side
    assert not np.asarray(grads["mlp"]["experts"]["router_bias"]).any()
    # every kind of leaf got one
    for name in ("w_in", "w_conv", "w_out"):
        assert np.asarray(grads["attn"]["conv"][name]).any()
    for name in ("wq", "wk", "q_norm", "k_norm"):
        assert np.asarray(jax.tree_util.tree_leaves(
            grads["attn"]["full_attention"][name])[0]).any()
    assert np.asarray(grads["mlp"]["experts"]["router"]).any()


def test_the_routing_margin_is_wider_than_rounding(params, batch):
    """The comparisons above are sound: no choice hangs on rounding."""
    x = pattern.pattern_forward(params, batch[0], CFG)[0]
    mp = jax.tree_util.tree_map(lambda p: p[0], params["mlp"]["experts"])
    h = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(h @ mp["router"]) + mp["router_bias"]
    top = jax.lax.top_k(s, CFG.experts_per_token + 1)[0]
    assert float(jnp.min(top[:, -2] - top[:, -1])) > 1e-5


@pytest.mark.parametrize("what", ["a later token", "another row"])
def test_the_convolution_is_causal_and_blind_across_the_batch(params,
                                                             what):
    lp = jax.tree_util.tree_map(lambda p: p[0], params["attn"]["conv"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    out = pattern.conv_mixer(lp, h, CFG)
    if what == "a later token":
        moved = pattern.conv_mixer(lp, h.at[0, 7].add(1.0), CFG)
        np.testing.assert_array_equal(out[0, :7], moved[0, :7])
        assert np.abs(np.asarray(out[0, 7:10] - moved[0, 7:10])).min() > 0
        # three taps: position 10 no longer sees position 7
        np.testing.assert_array_equal(out[0, 10:], moved[0, 10:])
    else:
        moved = pattern.conv_mixer(lp, h.at[1].add(1.0), CFG)
        np.testing.assert_array_equal(out[0], moved[0])
        # and the first tokens of a row see zeros, not the row before
        alone = pattern.conv_mixer(lp, h[1:], CFG)
        np.testing.assert_allclose(out[1], alone[0], atol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weights(params, batch):
    x = pattern.pattern_forward(params, batch[0], CFG)[0]
    h = x.reshape(-1, x.shape[-1])
    mp = jax.tree_util.tree_map(lambda p: p[0], params["mlp"]["experts"])
    plain_idx, plain_w = experts.route(mp["router"], h, CFG)
    idx, w = experts.route(mp["router"], h, CFG, mp["router_bias"])
    chosen = lambda i: np.sort(np.asarray(i), axis=1)
    moved = (chosen(idx) != chosen(plain_idx)).any(axis=1).mean()
    assert 0.05 < moved < 0.8            # some choices, not all (seen 0.3)
    # the weights are of the scores alone: the chosen scores renormalised
    s = np.asarray(jax.nn.sigmoid(h @ mp["router"]))
    top = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        w, top / (top.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-5)
    same = (chosen(idx) == chosen(plain_idx)).all(axis=1)
    np.testing.assert_allclose(np.sort(np.asarray(w)[same], axis=1),
                               np.sort(np.asarray(plain_w)[same], axis=1),
                               rtol=1e-6)
    # ten times the bias on one expert: every token chooses it
    bias = jnp.zeros_like(mp["router_bias"]).at[3].set(10.0)
    assert (np.asarray(experts.route(mp["router"], h, CFG, bias)[0])
            == 3).any(axis=1).all()


@pytest.fixture(scope="module")
def sparse_layer(params, batch):
    """(the layer's parameters with ALL its experts, normed tokens h)."""
    x = pattern.pattern_forward(params, batch[0], CFG)[0]
    mp = jax.tree_util.tree_map(lambda p: p[1], params["mlp"]["experts"])
    return mp, x.reshape(-1, x.shape[-1])


def share_of(mp, h, held):
    """A chip's share of the layer: out, counts, and the gradients of
    sum(out * probe) for the tokens and the held experts."""
    cfg = dataclasses.replace(CFG, experts_held=held)
    lo, hi = held
    mine = {n: w[lo:hi] for n, w in mp["experts"].items()}
    probe = jnp.cos(jnp.arange(h.size, dtype=jnp.float32)).reshape(h.shape)

    def f(h, mine, router):
        out, counts = experts.expert_layer_train(
            {"router": router, "router_bias": mp["router_bias"],
             "experts": mine}, h, cfg)
        return jnp.sum(out * probe), (out, counts)

    (_, (out, counts)), grads = jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True)(h, mine, mp["router"])
    return out, counts, grads


def test_the_four_shares_add_up_to_the_uncut_layer(sparse_layer):
    """Output AND the tokens' gradient: the parts the ranges (0, 4) ..
    (12, 16) give add up to the uncut layer's, nothing counted twice; the
    held experts' gradients are the uncut layer's rows for them; so is
    the plain reference's output."""
    mp, h = sparse_layer
    whole, counts, (dh, de, dr) = share_of(mp, h, (0, 16))
    parts = [share_of(mp, h, s) for s in SHARES]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=2e-5)
    np.testing.assert_allclose(sum(p[2][0] for p in parts), dh, atol=2e-5)
    np.testing.assert_allclose(sum(p[2][2] for p in parts), dr, atol=2e-5)
    for (lo, hi), p in zip(SHARES, parts):
        for n in ("wi", "wg", "wd"):
            np.testing.assert_allclose(p[2][1][n], de[n][lo:hi], atol=2e-5)
    # every pair lies in exactly one share's groups
    assert sum(int(p[1][2]) for p in parts) == int(counts[2]) \
        == h.shape[0] * CFG.experts_per_token
    with jax.default_matmul_precision("highest"):
        want = ref.experts(mp, h, M, share=(0, 16))
        want_share = ref.experts(
            {**mp, "experts": {n: w[4:8] for n, w in mp["experts"].items()}},
            h, M, share=(4, 8))
    np.testing.assert_allclose(whole, want, atol=2e-5)
    np.testing.assert_allclose(parts[1][0], want_share, atol=2e-5)


def test_an_expert_no_token_chose_gets_an_exactly_zero_gradient(
        sparse_layer):
    """`tgmm` over empty groups, and a pair whose expert is elsewhere adds
    nothing forward or backward."""
    mp, h = sparse_layer
    # the bias keeps every token off experts 5 and 6
    bias = mp["router_bias"].at[jnp.asarray([5, 6])].set(-10.0)
    out, counts, (dh, de, _) = share_of({**mp, "router_bias": bias}, h,
                                        (4, 8))
    assert int(counts[0]) == 2                      # experts 4 and 7
    for n in ("wi", "wg", "wd"):
        g = np.asarray(de[n])
        assert not g[1:3].any() and g[0].any() and g[3].any()
        assert np.isfinite(g).all()
    assert np.isfinite(np.asarray(dh)).all()
    # a token none of whose experts are here: zero out, zero gradient
    idx = np.asarray(experts.route(mp["router"], h, CFG, bias)[0])
    away = ~((idx >= 4) & (idx < 8)).any(axis=1)
    assert away.any()
    # (its gradient through the router's weights is zero too: its weights
    # multiply nothing)
    assert not np.asarray(out)[away].any()
    assert not np.asarray(dh)[away].any()


# -- the sorted rows that hold a pair (`experts._worked`) --------------------

CHUNK = 128          # `_CHUNK_TILES` 1 at the test's row tile of 128
# pairs that lie in a group of experts 0..3, of the 1024 of 256 tokens:
# none; inside the first chunk; on a chunk's edge; one past it; all
HERE = [0, 60, CHUNK, CHUNK + 1, 1024]
HELD = (0, 4)


def steered(total):
    """(mp with all 16 experts, h) of a layer in which, with experts 0..3
    held (`HELD`), exactly `total` of the 1024 (token, expert) pairs lie in
    a group: a token of kind A chooses experts 0..3 (four pairs here), of
    kind C 3..6 (one), of kind B 4..7 (none), by a feature of its own that
    the router reads at weight 1 (the other features are noise to it)."""
    N, D = 256, CFG.d_model
    a, c = divmod(total, 4)
    kind = np.full(N, 1)
    kind[np.random.RandomState(total).permutation(N)[:a + c]] = \
        [0] * a + [2] * c
    ks = jax.random.split(jax.random.PRNGKey(total), 6)
    h = jax.random.normal(ks[0], (N, D)).at[:, :3].set(
        10.0 * jax.nn.one_hot(kind, 3))
    router = 0.01 * jax.random.normal(ks[1], (D, 16))
    for row, chosen in enumerate([range(0, 4), range(4, 8), range(3, 7)]):
        router = router.at[row].set(
            jnp.zeros(16).at[jnp.asarray(chosen)].set(1.0))
    F = CFG.expert_ff
    mp = {"router": router, "router_bias": jnp.zeros(16),
          "experts": {"wi": 0.1 * jax.random.normal(ks[2], (16, D, F)),
                      "wg": 0.1 * jax.random.normal(ks[3], (16, D, F)),
                      "wd": 0.1 * jax.random.normal(ks[4], (16, F, D))}}
    return mp, h


@pytest.mark.parametrize("total", HERE)
def test_the_chunked_layer_is_the_single_pass_layer(total, monkeypatch):
    """`out`, the tokens' gradient, the three expert stacks' and the
    router's, with the sorted rows in eight chunks against one pass."""
    mp, h = steered(total)
    assert 1024 <= experts._chunk_rows(1024)        # one pass as shipped
    one = share_of(mp, h, HELD)
    monkeypatch.setattr(experts, "_CHUNK_TILES", 1)
    assert experts._chunk_rows(1024) == CHUNK
    cut = share_of(mp, h, HELD)
    assert int(one[1][2]) == int(cut[1][2]) == total
    # what each worked: every row, and the chunks up to the last pair
    assert int(one[1][3]) == 1024
    assert int(cut[1][3]) == -(-total // CHUNK) * CHUNK
    for got, want in zip(jax.tree_util.tree_leaves((cut[0], cut[2])),
                         jax.tree_util.tree_leaves((one[0], one[2]))):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    out, (dh, de, _) = cut[0], cut[2]
    if total:
        assert np.asarray(out).any() and np.asarray(dh).any()
        assert all(np.asarray(de[n]).any() for n in ("wi", "wg", "wd"))
    else:
        assert not np.asarray(out).any() and not np.asarray(dh).any()


@pytest.mark.parametrize("total", HERE)
def test_rows_in_no_group_read_zero(total, monkeypatch):
    """Every array of sorted rows that `_worked` makes, forward or
    backward, is zero from `total` on, though what it is made FROM is
    whatever a kernel left there (NaN here)."""
    monkeypatch.setattr(experts, "_CHUNK_TILES", 1)
    mp, h = steered(total)
    cfg = dataclasses.replace(CFG, experts_held=HELD)
    idx, _ = experts.route(mp["router"], h, cfg, mp["router_bias"])
    _, order, sizes = experts._sort_pairs(idx, cfg, None)
    assert int(jnp.sum(sizes)) == total
    t, inv = jnp.sum(sizes), jnp.argsort(order)
    R, D, F = 1024, h.shape[1], cfg.expert_ff
    dead = (jnp.arange(R) >= total)[:, None]
    left = lambda key, width: jnp.where(
        dead, jnp.nan, jax.random.normal(jax.random.PRNGKey(key), (R, width)))

    def zero_behind(rows):
        rows = np.asarray(rows)
        assert rows.shape[0] == R and not rows[total:].any()
        assert np.isfinite(rows).all()
        if total:
            assert rows[:total].any()

    xs, spread_back = jax.vjp(
        lambda h: experts._spread(h, order, inv, t), h)
    zero_behind(xs)
    np.testing.assert_array_equal(xs[:total], h[order[:total] // 4])
    _, twice_back = jax.vjp(lambda x: experts._twice(x, t), xs)
    zero_behind(twice_back((left(1, D), left(2, D)))[0])
    up, gate = left(3, F), left(4, F)
    mid, gated_back = jax.vjp(lambda u, g: experts._gated(u, g, t), up, gate)
    zero_behind(mid)
    for grad in gated_back(left(5, F)):
        zero_behind(grad)
    y = left(6, D)
    back, collect_back = jax.vjp(
        lambda y: experts._collect(y, order, inv, t), y)
    zero_behind(collect_back(jnp.ones((R, D)))[0])
    # the pairs' own order: a pair here reads its sorted row
    here = np.asarray(inv) < total
    assert np.isfinite(np.asarray(back)[here]).all()
    assert np.isnan(np.asarray(back)[~here]).all()


@pytest.mark.parametrize("dp", [1, 2])
def test_rows_worked_is_counted_and_summed_over_replicas(dp, monkeypatch):
    """`rows_worked`, the fourth of `TRAINED`: whole chunks, at least the
    pairs here and under a chunk more a replica; summed over `dp` as
    `pairs_here` is, where the two counts before it are the fullest
    replica's."""
    if len(jax.devices()) < dp:
        pytest.skip(f"needs {dp} devices")
    assert experts.TRAINED == experts.ROUTED + ("rows_worked",)
    assert experts.TRAINED[2:] == ("pairs_here", "rows_worked")
    monkeypatch.setattr(experts, "_CHUNK_TILES", 1)
    cfg = dataclasses.replace(CFG, experts_held=(0, 4))
    p = transformer_init(jax.random.PRNGKey(3), cfg)
    toks = weights.lm_tokens(weights.seed_key(5), 0, 2, T + 1, V)
    tokens, targets = toks[:, :-1], toks[:, 1:]
    opt = optax.sgd(0.0)
    mesh = create_hybrid_mesh(devices=jax.devices()[:dp], dp=dp)
    step, shard_state, shard_batch = make_train_step(mesh, cfg, opt)
    # a replica's own counts: its rows of the batch through the walk
    alone = [np.asarray(pattern.pattern_forward(p, rows, cfg)[1])
             for rows in np.split(np.asarray(tokens), dp)]
    sp, so = shard_state(jax.tree_util.tree_map(jnp.array, p), opt.init(p))
    c = np.asarray(step(sp, so, shard_batch((tokens, targets)))[3])
    assert c.shape == (experts.sparse_layers(cfg), len(experts.TRAINED))
    n = experts.TRAINED.index("pairs_here")
    np.testing.assert_array_equal(c[:, :n], np.max(alone, axis=0)[:, :n])
    np.testing.assert_array_equal(c[:, n:], np.sum(alone, axis=0)[:, n:])
    pairs, worked = c[:, 2], c[:, 3]
    chunk = experts._chunk_rows(tokens.size // dp * cfg.experts_per_token)
    assert chunk == CHUNK
    assert (worked % chunk == 0).all()
    assert (worked >= pairs).all() and (worked < pairs + dp * chunk).all()
    assert (worked < tokens.size * cfg.experts_per_token).all()  # skipped


def test_recomputation_changes_no_number(params, batch, monkeypatch):
    """Checkpointed (as the step always is) against the same walk with
    every intermediate kept, bitwise on the CPU."""
    a = jax.jit(jax.value_and_grad(program_loss))(params, *batch)
    monkeypatch.setattr(jax, "checkpoint", lambda f: f)
    b = jax.jit(jax.value_and_grad(
        lambda p, t, y: program_loss(p, t, y)))(params, *batch)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("dp", [1, 2])
def test_three_adamw_steps_follow_the_reference(params, dp):
    """Through `make_train_step` on a dp mesh: losses, the routing counts,
    the parameters after three steps; the bias takes no step."""
    if len(jax.devices()) < dp:
        pytest.skip(f"needs {dp} devices")
    hp = M["train"]["optimizer"]
    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])
    mesh = create_hybrid_mesh(devices=jax.devices()[:dp], dp=dp)
    step, shard_state, shard_batch = make_train_step(mesh, CFG, opt)
    copy = jax.tree_util.tree_map(jnp.array, params)
    p, o = shard_state(copy, opt.init(copy))
    batches = []
    for i in range(3):
        toks = weights.lm_tokens(weights.seed_key(5), i, 2, T + 1, V)
        batches.append((toks[:, :-1], toks[:, 1:]))
    losses, counts = [], []
    for b in batches:
        p, o, loss, c = step(p, o, shard_batch(b))
        losses.append(float(loss))
        counts.append(np.asarray(c))
    rp = params
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    mu, nu, want = zeros(rp), zeros(rp), []
    grad = jax.jit(jax.value_and_grad(reference_loss))
    for i, b in enumerate(batches):
        loss, g = grad(rp, *b)
        want.append(float(loss))
        rp, mu, nu = ref.adamw_step(rp, g, mu, nu, i + 1, hp)
    np.testing.assert_allclose(losses, want, rtol=2e-5)
    for path, gap in jax.tree_util.tree_leaves_with_path(rel(
            jax.tree_util.tree_map(lambda a, b: a - b, p, params),
            jax.tree_util.tree_map(lambda a, b: a - b, rp, params))):
        assert gap <= 5e-3, (jax.tree_util.keystr(path), gap)
    np.testing.assert_array_equal(
        p["mlp"]["experts"]["router_bias"],
        params["mlp"]["experts"]["router_bias"])
    c = np.stack(counts)                 # [steps, sparse layers, TRAINED]
    assert c.shape == (3, 2, len(experts.TRAINED))
    assert (c[..., 2] == 2 * T * CFG.experts_per_token).all()   # all held
    assert (c[..., 3] == c[..., 2]).all()    # one pass a replica: every row
    # the fullest replica's fullest expert; the replicas' pairs together
    assert (c[..., 0] <= 16).all()
    assert (c[..., 1] >= c[..., 2] / 16 / dp).all()


def test_a_model_without_experts_returns_three(params):
    cfg = dataclasses.replace(CFG, n_layers=1, layer_attn=("conv",),
                              layer_mlp=("dense",))
    opt = optax.sgd(0.1)
    mesh = create_hybrid_mesh(devices=jax.devices()[:1], dp=1)
    step, shard_state, shard_batch = make_train_step(mesh, cfg, opt)
    p = transformer_init(jax.random.PRNGKey(0), cfg)
    p, o = shard_state(p, opt.init(p))
    toks = jnp.zeros((1, 8), jnp.int32)
    out = step(p, o, shard_batch((toks, toks)))
    assert len(out) == 3 and np.isfinite(float(out[2]))


def test_init_gives_the_tree_the_walk_takes():
    cfg = dataclasses.replace(CFG, experts_held=(4, 12))
    p = transformer_init(jax.random.PRNGKey(0), cfg)
    want = jax.eval_shape(lambda: W.params(
        jax.random.PRNGKey(0), {**M, "experts_held": [4, 12]}, jnp.float32))
    assert jax.tree_util.tree_map(lambda a: a.shape, p) == \
        jax.tree_util.tree_map(lambda a: a.shape, want)
    assert p["mlp"]["experts"]["experts"]["wi"].shape == (2, 8, 64, 32)
    assert p["mlp"]["experts"]["router_bias"].shape == (2, 16)
    assert p["attn"]["conv"]["w_conv"].shape == (2, 64, 3)
    assert p["attn"]["full_attention"]["q_norm"]["scale"].shape == (1, 16)


WINDOWED = dataclasses.replace(CFG, attn_specs=(
    ("conv", ConvSpec(3)), ("full_attention", AttnSpec(4, window=8))))
MESH1 = lambda **axes: type("M", (), {"shape": axes})()
REFUSALS = {
    "a tp axis": lambda p: make_train_step(MESH1(dp=1, tp=2), CFG, None),
    "a sp axis": lambda p: make_train_step(MESH1(sp=2), CFG, None),
    "a pp axis": lambda p: make_train_step(MESH1(pp=2), CFG, None),
    "a ep axis": lambda p: make_train_step(MESH1(ep=2), CFG, None),
    "an attention window": lambda p: make_train_step(
        MESH1(dp=1), WINDOWED, None),
    "a gate a head": lambda p: make_train_step(
        MESH1(dp=1), dataclasses.replace(CFG, attn_gate=True), None),
    "a shared expert": lambda p: make_train_step(
        MESH1(dp=1), dataclasses.replace(CFG, shared_ff=32), None),
    "gated short convolution": lambda p: init_decode_cache(CFG, 1, 8),
    "short convolution": lambda p: transformer_generate(
        p, CFG, jnp.zeros((1, 4), jnp.int32), 2),
    "convolution": lambda p: InferenceServer(
        p, CFG, max_seq_tokens=16, max_batch=2, page_tokens=4),
    "norms q and k": lambda p: init_decode_cache(
        dataclasses.replace(
            CFG, n_layers=1, layer_attn=("full_attention",),
            layer_mlp=("dense",),
            attn_specs=(("full_attention", AttnSpec(4, qk_norm=True)),)),
        1, 8),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_by_name(params, what):
    """What of a pattern is still not run raises, and names it."""
    with pytest.raises(HorovodTpuError, match=what):
        REFUSALS[what](params)


def test_config_refuses_a_convolution_of_no_taps():
    with pytest.raises(ValueError, match="taps"):
        dataclasses.replace(CFG, attn_specs=(
            ("conv", ConvSpec(0)), CFG.attn_specs[1]))


def test_uniform_and_served_models_keep_their_programs():
    """`mistral7b_train_4k`'s train step and a served pattern's decode
    step and prefill (Laguna's kind: routed experts beside a shared one,
    no bias) lower to the same text as at the parent commit: the new fields at
    their defaults add no operation.  The train step's digest was taken
    at the parent (247f370) with this very code; the served pattern's two
    at PR 42, whose expert layer counts a third number a sparse layer
    (`experts.ROUTED`: `pairs_here`) and nothing else new at the defaults
    (no groups, no latent kind; a pass is one pass at these sizes).  The
    served pattern's PREFILL was taken anew at PR 44, which is the change
    it was there to catch: its 200 tokens, padded to 256, ran four steps a
    head of the flash kernel's own 128 x 128 and run one tile of 256 now
    (`decode.prompt_tiles`; tests/test_prompt_tiles.py holds the rule,
    the results at every tile, and the two callers whose tiles did not
    move).  A change of JAX moves all three."""
    import hashlib
    from horovod_tpu.models import transformer_decode_step
    digest = lambda lowered: hashlib.sha256(
        lowered.as_text().encode()).hexdigest()[:16]
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=2, n_kv_heads=2,
                            attn_window=16)
    opt = optax.adamw(3e-4)
    mesh = create_hybrid_mesh(devices=jax.devices()[:1], dp=1)
    step, _, _ = make_train_step(mesh, cfg, opt)
    p = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    train = digest(step.lower(p, jax.eval_shape(opt.init, p), (toks, toks)))
    pat = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, n_kv_heads=2, layer_attn=("full", "full"),
        layer_mlp=("dense", "experts"), attn_specs=(("full", AttnSpec(4)),),
        n_experts=8, experts_per_token=2, expert_ff=16, shared_ff=16,
        routed_scale=2.5, attn_gate=True)
    pp = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0), pat))
    cache = jax.eval_shape(lambda: init_decode_cache(pat, 2, 16))
    decode = digest(jax.jit(
        lambda p, c, t: transformer_decode_step(p, c, t, pat)).lower(
            pp, cache, jax.ShapeDtypeStruct((2,), jnp.int32)))
    # and its prefill of 200 tokens, which takes the flash kernel at the
    # tiles `decode.prompt_tiles` fits to them, as training does
    from horovod_tpu.models import transformer_prefill
    prefill = digest(jax.jit(
        lambda p, c, t: transformer_prefill(p, c, t, pat)).lower(
            pp, jax.eval_shape(lambda: init_decode_cache(pat, 1, 256)),
            jax.ShapeDtypeStruct((1, 200), jnp.int32)))
    assert (train, decode, prefill) == PARENT_DIGESTS


PARENT_DIGESTS = ("cc76927979bf3144", "cffacc3d7f10915b",
                  "b20b100984ea497a")
