"""ops/retention_step.py, interpreted on the CPU: the kernel that makes a
retention layer's pass over the state (read-out and update, in place,
over the rows that are live) against the three einsums on the same
inputs (models/decode.py `_state_pass`), and the shape rule that picks
between them in `_retention_decode_layer`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (TransformerConfig, init_decode_cache,
                                transformer_decode_step, transformer_init,
                                transformer_prefill)
from horovod_tpu.models import decode as D
from horovod_tpu.ops import retention_step as RS

DH = 128
DF = D.retention_features(DH)       # 8320 = 65 x 128: tiles of 1664


def _inputs(live, Hkv=2, g=2, L=2, decay=None, seed=0):
    B = len(live)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        fq=jax.random.normal(ks[0], (B, Hkv, g, DF), jnp.float32),
        fk=jax.random.normal(ks[1], (B, Hkv, DF), jnp.float32),
        v0=jax.random.normal(ks[2], (B, Hkv, DH), jnp.float32),
        decay=(jax.random.uniform(ks[3], (B, Hkv), jnp.float32)
               if decay is None else jnp.full((B, Hkv), decay, jnp.float32)),
        cs=jax.random.normal(ks[4], (L, B, Hkv, DF, DH), jnp.float32),
        cz=jax.random.normal(ks[5], (L, B, Hkv, DF), jnp.float32))


def _kernel(a, layer, live):
    live = None if live is None else jnp.asarray(live, bool)
    return [np.asarray(x) for x in jax.jit(
        lambda a: RS.retention_step(**a, layer=layer, live=live))(a)]


def _einsums(a, layer):
    return [np.asarray(x) for x in jax.jit(
        lambda a: D._state_pass(a["fq"], a["fk"], a["v0"], a["decay"],
                                a["cs"], a["cz"], layer))(a)]


def _close(got, want, what):
    # sums over 8320 products of order 1, grouped otherwise: float32
    # round-off on values of order 100
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4,
                               err_msg=what)


# `live` a row (0: idle)
CASES = {
    "idle_rows_between_and_at_both_ends": dict(live=[0, 1, 0, 1, 1, 0]),
    "every_row_live": dict(live=[1, 1, 1]),
    "one_live_row_last": dict(live=[0, 0, 1]),
    "no_live_row": dict(live=[0, 0]),
    "g5_over_8_kv_heads": dict(live=[1, 0], Hkv=8, g=5, L=1),
    "g7_fills_the_block": dict(live=[0, 1], Hkv=1, g=7, L=1),
    "decay_0": dict(live=[1, 0, 1], decay=0.0),
    "decay_1": dict(live=[1, 0, 1], decay=1.0),
    "layer_1_of_3": dict(live=[1, 1, 0], L=3, layer=1),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_matches_the_einsums(case):
    """On the live rows: what `fq` reads out of the state as it was, and
    the state decayed and added to, as the einsums give them.  Every
    other layer, and an idle row's state and normaliser, are bit for bit
    what went in; an idle row reads out 0."""
    c = dict(CASES[case])
    live, layer = c.pop("live"), c.pop("layer", 0)
    a = _inputs(live, **c)
    num, den, cs, cz = _kernel(a, layer, live)
    wnum, wden, wcs, wcz = _einsums(a, layer)
    lv = np.asarray(live, bool)
    _close(num[lv], wnum[lv], "num")
    _close(den[lv], wden[lv], "den")
    _close(cs[layer][lv], wcs[layer][lv], "state")
    _close(cz[layer][lv], wcz[layer][lv], "normaliser")
    assert not num[~lv].any() and not den[~lv].any()
    was_s, was_z = np.asarray(a["cs"]), np.asarray(a["cz"])
    np.testing.assert_array_equal(cs[layer][~lv], was_s[layer][~lv])
    np.testing.assert_array_equal(cz[layer][~lv], was_z[layer][~lv])
    others = [i for i in range(cs.shape[0]) if i != layer]
    np.testing.assert_array_equal(cs[others], was_s[others])
    np.testing.assert_array_equal(cz[others], was_z[others])


def test_every_row_form_is_the_live_rows_form_on_the_live_rows():
    """`live=None` (a scalar `pos`: every row is somebody's) steps the
    rows the mask names exactly as the masked call does, bit for bit."""
    live = [0, 1, 1, 0]
    a = _inputs(live, seed=3)
    lv = np.asarray(live, bool)
    some, every = _kernel(a, 1, live), _kernel(a, 1, None)
    for s, e in zip(some[:2], every[:2]):
        np.testing.assert_array_equal(s[lv], e[lv])
    for s, e in zip(some[2:], every[2:]):
        np.testing.assert_array_equal(s[1][lv], e[1][lv])
    # and the rows the mask left out were stepped too
    assert (every[2][1][~lv] != np.asarray(a["cs"])[1][~lv]).any()


def test_donated_state_comes_back_in_its_own_buffers():
    """The stacked leaves go in donated and come out aliased: the
    compiled program holds no second state."""
    a = _inputs([1, 0, 1])
    step = jax.jit(
        lambda cs, cz, a: RS.retention_step(
            a["fq"], a["fk"], a["v0"], a["decay"], cs, cz, 0,
            jnp.asarray([1, 0, 1], bool))[2:],
        donate_argnums=(0, 1))
    cs, cz = a.pop("cs"), a.pop("cz")
    text = step.lower(cs, cz, a).as_text()
    assert text.count("tf.aliasing_output") == 2
    state = cs.nbytes + cz.nbytes
    compiled = step.lower(cs, cz, a).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == state
    out = step(cs, cz, a)
    assert cs.is_deleted() and cz.is_deleted()
    assert [o.shape for o in out] == [cs.shape, cz.shape]


@pytest.mark.parametrize("kw,kernel", [
    (dict(d_head=128), True),
    (dict(d_head=128, state_dtype=jnp.bfloat16), False),
    (dict(d_head=8), False),            # Df 40: no tile of 128
    (dict(d_head=64), False),           # rows of 64 fill no lane tile
], ids=["f32_dh128", "bf16_state", "df_does_not_tile", "dh64"])
def test_shape_and_type_pick_the_pass(kw, kernel):
    """`_retention_decode_layer` hands the kernel a float32 state whose
    rows fill lanes and whose `Df` tiles; a bfloat16 state and the tiny
    shapes keep the einsums.  Either way the step gives the einsums'
    logits."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, d_ff=64,
                            n_layers=2, n_kv_heads=2,
                            compute_dtype=jnp.float32,
                            attn_kind="retention", **kw)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    assert RS.takes(init_decode_cache(cfg, 3, 1)["s"]) == kernel
    # rows behind prompts of 4 and 9 tokens, an idle row between them
    # that still holds what a request left there
    rng = np.random.RandomState(1)
    rows = [transformer_prefill(
        params, init_decode_cache(cfg, 1, 1),
        jnp.asarray(rng.randint(0, 64, size=n))[None], cfg, chunk=4)[1]
        for n in (4, 6, 9)]
    cache = {n: jnp.concatenate([r[n] for r in rows], axis=1)
             for n in ("s", "z")}
    cache["pos"] = jnp.asarray([4, 0, 9], jnp.int32)
    feed = jnp.asarray([5, 6, 7], jnp.int32)
    step = jax.jit(lambda p, c, f: transformer_decode_step(p, c, f, cfg))
    assert ("pallas_call" in str(jax.make_jaxpr(step)(params, cache, feed))
            ) == kernel
    lg, got = step(params, cache, feed)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(RS, "takes", lambda cs: False)
        wlg, want = jax.jit(
            lambda p, c, f: transformer_decode_step(p, c, f, cfg))(
                params, cache, feed)
    live = np.asarray(cache["pos"]) > 0
    np.testing.assert_allclose(np.asarray(lg)[live], np.asarray(wlg)[live],
                               atol=2e-5)
    assert np.isfinite(np.asarray(lg)).all()
    for n in ("s", "z"):
        np.testing.assert_allclose(
            np.asarray(got[n], np.float32)[:, live],
            np.asarray(want[n], np.float32)[:, live], rtol=1e-5, atol=1e-5)
    if kernel:
        np.testing.assert_array_equal(np.asarray(got["s"])[:, ~live],
                                      np.asarray(cache["s"])[:, ~live])


@pytest.mark.parametrize("positions,want", [
    ([0, 0, 0, 0], 0.0), ([3, 0, 9, 0], 50.0), ([1, 2, 3, 4], 100.0),
    ([0] * 6 + [700] * 10, 62.5)])
def test_read_pct_counts_the_live_rows(positions, want):
    assert RS.read_pct(np.asarray(positions)) == pytest.approx(want)


@pytest.mark.parametrize("features,rows", [
    (8320, 1664), (40, 0), (128, 128), (129 * 256, 768), (4096, 2048)])
def test_tile_rows(features, rows):
    assert RS.tile_rows(features) == rows
