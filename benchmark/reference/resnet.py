"""Plain reference of ResNet-50 v1.5 as `models/resnet.py` trains it:
NHWC, 7x7/2 stem, 3x3/2 max pool, bottleneck blocks with the stride on
the 3x3, batch norm on the batch's own statistics (over the whole global
batch, eps 1e-5), mean cross-entropy, SGD with momentum.  float32 at
"highest"; each block is rematerialised in the backward pass so that 256
images a chip fit in float32.  `precision` "fp8" rounds both operands of
every convolution and of the classifier to float8_e4m3 (the control)."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.lib.weights import resnet_blocks

BN_EPS = 1e-5


def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def conv(p, x, stride: int, precision: str):
    return jax.lax.conv_general_dilated(
        _q(x, precision), _q(p["kernel"], precision), (stride, stride),
        "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def batchnorm(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def block(p, x, stride: int, precision: str):
    y = jax.nn.relu(batchnorm(p["bn1"], conv(p["conv1"], x, 1, precision)))
    y = jax.nn.relu(batchnorm(p["bn2"],
                              conv(p["conv2"], y, stride, precision)))
    y = batchnorm(p["bn3"], conv(p["conv3"], y, 1, precision))
    if "proj" in p:
        x = batchnorm(p["bn_proj"], conv(p["proj"], x, stride, precision))
    return jax.nn.relu(y + x)


def logits(params: Dict, x, m: Dict, precision: str = "f32"):
    y = jax.nn.relu(batchnorm(params["bn_stem"],
                              conv(params["stem"], x, 2, precision)))
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for name, _, _, stride in resnet_blocks(m):
        y = jax.checkpoint(functools.partial(
            block, stride=stride, precision=precision))(params[name], y)
    y = jnp.mean(y, axis=(1, 2))
    head = params["head"]
    return jnp.matmul(_q(y, precision), _q(head["kernel"], precision),
                      precision=jax.lax.Precision.HIGHEST) + head["bias"]


def loss(params: Dict, x, labels, m: Dict, precision: str = "f32"):
    lg = logits(params, x, m, precision)
    logp = jax.nn.log_softmax(lg)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def sgd_step(params, grads, trace, hp: Dict):
    """Momentum SGD as optax.sgd defines it: trace = g + momentum*trace."""
    tm = jax.tree_util.tree_map
    trace = tm(lambda t, g: g + hp["momentum"] * t, trace, grads)
    params = tm(lambda p, t: p - hp["learning_rate"] * t, params, trace)
    return params, trace
