"""The flash kernel's tiles in a prompt pass (`models/decode.py`
`prompt_tiles`, `_flash_prompt`): the rule itself over lengths 128 to
16384, a tiny pattern's prefill at every tile the rule can pick against
the kernel's own 128 x 128 and against the dense path, and the programs
of the two callers that had tiles of their own (training's
`pattern.attention_mixer`, a latent kind's expanded prompt) held to the
text they lowered to with them.  The kernels run interpreted on the CPU;
what a tile costs is the chip's to say (PERF.md 6, PR 44).
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (TransformerConfig, decode,
                                init_decode_cache, make_train_step,
                                transformer_init, transformer_prefill)
from horovod_tpu.models.transformer import AttnSpec
from horovod_tpu.parallel import create_hybrid_mesh
from horovod_tpu.parallel import sequence as seq_mod
# LFM2-8B-A1B's and GigaChat3.1's shapes at a test's size
from test_conv_moe_train import M as LFM2
from test_latent import M as GIGA

LENGTHS = (128, 129, 200, 255, 256, 257, 384, 500, 512, 513, 1000, 1024,
           1025, 1100, 1536, 2048, 2049, 2944, 3072, 3584, 4096, 5000, 6144,
           7168, 8192, 12288, 16383, 16384)
# laguna_xs2_codegen_steady's six prompt lengths (traffic/codegen_steady.
# json) and the tiles PERF.md 6 (PR 44) reports for them
CELL_TILES = {512: 512, 1024: 1024, 2048: 1024, 3072: 1024, 4096: 1024,
              6144: 1024}
# what ran under the two constants this rule replaced, (1024, 1024) each:
# lfm2_8b_train_8k's sequences and gigachat702b_longctx_steady's prompts
HAD_1024 = (8192, 4096, 6144, 12288, 16384)


@pytest.mark.parametrize("T", LENGTHS)
def test_the_rule(T):
    """Tiles are square multiples of 128 of at most 1024 rows that the
    padded length is a whole number of; no length of 256 or more runs a
    tile of 128; the padding is under 128 + T / 8 tokens, and under 128
    (what the kernel's own tile asked) up to 1024 tokens."""
    padded, (bq, bk) = decode.prompt_tiles(T, 128)
    assert bq == bk and bq % 128 == 0 and 128 <= bq <= 1024
    assert padded >= T and padded % bq == 0
    assert padded - T < 128 + T / 8
    if T <= 1024:
        assert padded - T < 128 and bq == padded
    if T >= 256:
        assert bq >= 256
    # no fewer tiles of that size would hold the prompt
    assert padded - bq < T
    if T in CELL_TILES:
        assert (padded, bq) == (T, CELL_TILES[T])
    if T in HAD_1024:
        assert (padded, bq) == (T, 1024)


@pytest.mark.parametrize("T,want", [(1100, (1280, 640)), (2944, (3072, 1024)),
                                    (200, (256, 256)), (1025, (1280, 640))])
def test_lengths_no_cell_sends(T, want):
    """1100 tokens are two tiles of 640 (not one of 2048, nor 81 of 128);
    23 x 128 tokens, which the kernel's own clamp ran at 128 x 128, are
    three tiles of 1024."""
    padded, (bq, _) = decode.prompt_tiles(T, 128)
    assert (padded, bq) == want


@pytest.mark.parametrize("d_head,tile", [(64, 1024), (192, 1024), (512, 1024),
                                         (768, 512), (1024, 512),
                                         (2048, 256)])
def test_wide_heads_halve_the_tile(d_head, tile):
    """1024 rows up to heads of 512, where Mosaic still fits the forward
    and the backward kernels into the v5e's VMEM (compiled for a described
    chip, PR 44: the backward fails at 768); every cell's heads are 64 to
    192 wide."""
    assert decode.prompt_tiles(4096, d_head) == (4096, (tile, tile))


WINDOW = 96


def tiny(kind: str) -> TransformerConfig:
    """A full-attention layer, then one of `kind` ("full", or "window":
    one ring of 96 slots, which no tile here is a multiple of); 4 heads
    on 2 of 16, a gate a head, dense MLPs: no routing, so nothing
    discrete."""
    specs = (("full", AttnSpec(4)), ("window", AttnSpec(4, window=WINDOW)))
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, d_head=16, d_ff=64,
        n_layers=2, n_kv_heads=2, layer_attn=("full", kind),
        layer_mlp=("dense", "dense"),
        attn_specs=specs[:1 + (kind == "window")], attn_gate=True,
        compute_dtype=jnp.float32)


def prefilled(cfg, T, monkeypatch, most=None, dense=False):
    """(logits, cache) of one prompt of T tokens, the widest tile held to
    `most` rows, or the dense path in the kernel's place."""
    if most:
        monkeypatch.setattr(decode, "_PROMPT_TILE", most)
    if dense:
        monkeypatch.setattr(
            decode, "_flash_prompt",
            lambda q, k, v, window: seq_mod.full_attention(
                q, k, v, causal=True, window=window))
    params = transformer_init(jax.random.PRNGKey(1), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(T), (1, T), 0, 64)
    lg, cache = jax.jit(
        lambda p, c, t: transformer_prefill(p, c, t, cfg))(
            params, init_decode_cache(cfg, 1, T + 8), prompt)
    return np.asarray(lg), jax.tree_util.tree_map(np.asarray, cache)


# (prompt tokens, the widest tile allowed) -> (padded, tile): one tile
# and several, lengths that are and are not a multiple of their tile,
# a tile that is no power of two
PICKS = {(256, 256): (256, 256), (256, 1024): (256, 256),
         (300, 256): (512, 256), (300, 1024): (384, 384),
         (640, 256): (768, 256), (640, 512): (768, 384),
         (640, 1024): (640, 640), (700, 256): (768, 256)}


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("T,most", sorted(PICKS))
def test_a_prompt_pass_is_the_same_at_every_tile(kind, T, most, monkeypatch):
    """Logits and every leaf of the cache as at 128 x 128 and as
    `full_attention` gives them (float32: the sums' order alone
    differs)."""
    cfg = tiny(kind)
    with monkeypatch.context() as mp:
        got = prefilled(cfg, T, mp, most=most)
        padded, tile = PICKS[T, most]
        assert decode.prompt_tiles(T, cfg.d_head) == (padded, (tile, tile))
    with monkeypatch.context() as mp:
        small = prefilled(cfg, T, mp, most=128)
    with monkeypatch.context() as mp:
        dense = prefilled(cfg, T, mp, dense=True)
    for want in (small, dense):
        np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=0)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5, rtol=0),
            got[1], want[1])


def test_the_tile_is_in_the_lowered_program():
    """Nothing is decided at run time: a prompt length has one program
    and the program one tile, which its text shows: the one square
    float32 array in it is a tile's scores (block_q x block_k; the kernel
    is interpreted here, so its body is in the text)."""
    cfg = tiny("window")
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(1), cfg))
    for T, tile in ((200, 256), (300, 384), (1100, 640), (2048, 1024),
                    (2944, 1024)):
        text = jax.jit(
            lambda p, c, t: transformer_prefill(p, c, t, cfg)).lower(
                params, jax.eval_shape(
                    lambda: init_decode_cache(cfg, 1, T + 8)),
                jax.ShapeDtypeStruct((1, T), jnp.int32)).as_text()
        squares = {int(a) for a, b in re.findall(
            r"tensor<(\d+)x(\d+)xf32>", text) if a == b} - {1}
        assert squares == {tile}, (T, squares)


digest = lambda lowered: hashlib.sha256(
    lowered.as_text().encode()).hexdigest()[:16]

def _lfm2_step():
    from benchmark.runners import conv_moe_train
    cfg = conv_moe_train.transformer_config(LFM2, jnp.float32)
    opt = optax.adamw(3e-4)
    mesh = create_hybrid_mesh(devices=jax.devices()[:1], dp=1)
    step, _, _ = make_train_step(mesh, cfg, opt)
    p = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    return step.lower(p, jax.eval_shape(opt.init, p), (toks, toks))


def _latent_prefill():
    from benchmark.runners import latent_serve
    cfg = latent_serve.transformer_config(GIGA, jnp.float32)
    p = jax.eval_shape(lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    return jax.jit(lambda p, c, t: transformer_prefill(p, c, t, cfg)).lower(
        p, jax.eval_shape(lambda: init_decode_cache(cfg, 1, 2104)),
        jax.ShapeDtypeStruct((1, 2048), jnp.int32))


# Taken at the parent (9c430fb), where training passed
# `pattern.FLASH_BLOCKS` and the latent prompt `_LATENT_FLASH_BLOCKS`,
# (1024, 1024) each, clamped to a divisor of the length: a train step of
# LFM2's kind over 256 tokens (one tile, forward and backward kernels)
# and a prefill of GigaChat's kind over 2048 (two tiles of 1024).  A
# change of JAX moves both.
PARENT_TEXT = {"lfm2_step": "40b93c4e39d6cc25",
               "latent_prefill": "468e82aad762e510"}


@pytest.mark.parametrize("name,program", [("lfm2_step", _lfm2_step),
                                          ("latent_prefill",
                                           _latent_prefill)])
def test_the_callers_that_had_tiles_keep_their_programs(name, program):
    """`lfm2_8b_train_8k` and `gigachat702b_longctx_steady` run the tiles
    they ran: at the lengths their cells send (`HAD_1024`, above) the rule
    gives their constants' tiles, and the programs lower to the parent's
    text, so a compile cache the parent filled still serves them."""
    assert digest(program()) == PARENT_TEXT[name]
