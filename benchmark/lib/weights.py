"""Weights, token ids and images made on the device from `--seed`.

The benchmark makes them (not the program), so that the program and the
plain reference are handed the same values and neither takes anything
the other produced.  Every layer of a stacked leaf is drawn from its own
key, `fold_in(leaf_key, layer)`, so the reference can draw one layer at a
time and never holds the whole model in float32.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

LM_LEAVES = ("wq", "wk", "wv", "wo", "wi", "wg", "wd")


def seed_key(seed: int):
    """A key from any non-negative whole number (seeds run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _lm_shapes(m: Dict) -> Dict:
    D, H, Hkv, Dh, F = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    s_d, s_f, s_hd = 1 / math.sqrt(D), 1 / math.sqrt(F), 1 / math.sqrt(H * Dh)
    return {"wq": ((D, H, Dh), s_d), "wk": ((D, Hkv, Dh), s_d),
            "wv": ((D, Hkv, Dh), s_d), "wo": ((H, Dh, D), s_hd),
            "wi": ((D, F), s_d), "wg": ((D, F), s_d), "wd": ((F, D), s_f)}


def lm_leaf_layer(key, m: Dict, name: str, layer, dtype):
    """Layer `layer` of stacked leaf `name` (one of LM_LEAVES)."""
    shape, scale = _lm_shapes(m)[name]
    k = jax.random.fold_in(jax.random.fold_in(key, LM_LEAVES.index(name) + 1),
                           layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def lm_embed(key, m: Dict, dtype):
    k = jax.random.fold_in(key, 0)
    return (jax.random.normal(k, (m["vocab_size"], m["hidden_size"]),
                              jnp.float32)
            / math.sqrt(m["hidden_size"])).astype(dtype)


def lm_layer(key, m: Dict, layer, dtype) -> Dict:
    """All of one layer's weights, unstacked; norm scales are ones."""
    lp = {n: lm_leaf_layer(key, m, n, layer, dtype) for n in LM_LEAVES}
    ones = jnp.ones((m["hidden_size"],), jnp.float32)
    lp["ln1"] = {"scale": ones}
    lp["ln2"] = {"scale": ones}
    return lp


def lm_params(key, m: Dict, dtype) -> Dict:
    """The whole tree in the layout `models/transformer.py` trains and
    serves: leaves stacked over layers."""
    n = m["num_hidden_layers"]
    blocks = jax.vmap(lambda l: lm_layer(key, m, l, dtype))(jnp.arange(n))
    return {"embed": lm_embed(key, m, dtype),
            "final_norm": {"scale": jnp.ones((m["hidden_size"],),
                                             jnp.float32)},
            "blocks": blocks}


def lm_tokens(key, batch_index: int, rows: int, length: int, vocab: int):
    """`rows` token rows of `length`, all different, for batch number
    `batch_index` (fold 1000 keeps them apart from the weights' keys)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1000), batch_index)
    return jax.random.randint(k, (rows, length), 0, vocab, jnp.int32)


# -- ResNet v1.5 --------------------------------------------------------

RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
RESNET_WIDTHS = (64, 128, 256, 512)


def _conv(key, k: int, cin: int, cout: int):
    std = math.sqrt(2.0 / (k * k * cin))
    return {"kernel": jax.random.normal(key, (k, k, cin, cout),
                                        jnp.float32) * std}


def _bn(ch: int):
    return ({"scale": jnp.ones((ch,), jnp.float32),
             "bias": jnp.zeros((ch,), jnp.float32)},
            {"mean": jnp.zeros((ch,), jnp.float32),
             "var": jnp.ones((ch,), jnp.float32)})


def resnet_blocks(m: Dict):
    """(name, cin, width, stride) of every bottleneck block, in order."""
    cin = 64
    for stage, (n, w) in enumerate(zip(RESNET_STAGES[m["depth"]],
                                       RESNET_WIDTHS)):
        for b in range(n):
            yield (f"stage{stage}_block{b}", cin, w,
                   2 if (b == 0 and stage > 0) else 1)
            cin = 4 * w


def resnet_variables(key, m: Dict):
    """(params, batch_stats) in the layout `models/resnet.py` applies:
    He-normal kernels (HWIO), unit batch norm, a uniform classifier."""
    params, stats = {}, {}
    n = [0]

    def k():
        n[0] += 1
        return jax.random.fold_in(key, n[0])

    params["stem"] = _conv(k(), 7, 3, 64)
    params["bn_stem"], stats["bn_stem"] = _bn(64)
    for name, cin, w, stride in resnet_blocks(m):
        p, s = {}, {}
        p["conv1"], p["conv2"], p["conv3"] = (
            _conv(k(), 1, cin, w), _conv(k(), 3, w, w),
            _conv(k(), 1, w, 4 * w))
        for i, ch in (("1", w), ("2", w), ("3", 4 * w)):
            p[f"bn{i}"], s[f"bn{i}"] = _bn(ch)
        if stride != 1 or cin != 4 * w:
            p["proj"] = _conv(k(), 1, cin, 4 * w)
            p["bn_proj"], s["bn_proj"] = _bn(4 * w)
        params[name], stats[name] = p, s
    fan = 4 * RESNET_WIDTHS[-1]
    bound = 1.0 / math.sqrt(fan)
    params["head"] = {
        "kernel": jax.random.uniform(k(), (fan, m["num_classes"]),
                                     jnp.float32, -bound, bound),
        "bias": jax.random.uniform(k(), (m["num_classes"],), jnp.float32,
                                   -bound, bound)}
    return params, stats


def images(key, batch_index: int, rows: int, size: int, classes: int):
    """`rows` random images in [0, 1) and their labels, as the
    reference's synthetic benchmark feeds."""
    k = jax.random.fold_in(jax.random.fold_in(key, 2000), batch_index)
    kx, ky = jax.random.split(k)
    return (jax.random.uniform(kx, (rows, size, size, 3), jnp.float32),
            jax.random.randint(ky, (rows,), 0, classes, jnp.int32))
