"""A decoder LM trained through `make_train_step` (models/transformer.py)
with AdamW, bf16 compute over f32 parameters, on resident batches."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import optax

from benchmark.lib import traincheck, weights
from benchmark.reference import transformer as ref

CHECK_STEPS = 3


def _sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def grad_norms_from_mu(mu: Dict, b1: float) -> Dict[str, float]:
    """After one Adam step mu = (1 - b1) g: the gradient's norm by leaf."""
    flat = flat_norms(mu)
    return {k: v / (1 - b1) for k, v in flat.items()}


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(lambda x: jnp.sqrt(_sq(x)), tree)


def flat_norms(tree) -> Dict[str, float]:
    out = {}
    for path, v in jax.tree_util.tree_leaves_with_path(_norms(tree)):
        out[jax.tree_util.keystr(path)] = float(v)
    return out


def delta_norms(params: Dict, key, m: Dict) -> Dict[str, float]:
    """Norm of (params - the seed's initial weights) by leaf; the initial
    weights are drawn again a layer at a time, never held whole."""
    L = m["num_hidden_layers"]
    out = {}
    for name in weights.LM_LEAVES:
        f = jax.jit(lambda p, k, name=name: jnp.sqrt(jnp.sum(jax.lax.map(
            lambda l: _sq(p[l] - weights.lm_leaf_layer(
                k, m, name, l, jnp.float32)), jnp.arange(L)))))
        out[f"['blocks']['{name}']"] = float(f(params["blocks"][name], key))
    emb = jax.jit(lambda p, k: jnp.sqrt(_sq(
        p - weights.lm_embed(k, m, jnp.float32))))
    out["['embed']"] = float(emb(params["embed"], key))
    ones = jax.jit(lambda p: jnp.sqrt(_sq(p - 1.0)))
    out["['final_norm']['scale']"] = float(ones(params["final_norm"]["scale"]))
    for ln in ("ln1", "ln2"):
        out[f"['blocks']['{ln}']['scale']"] = float(
            ones(params["blocks"][ln]["scale"]))
    return out


class Runner:
    def __init__(self, ctx):
        from horovod_tpu.models import TransformerConfig, make_train_step
        from horovod_tpu.parallel import create_hybrid_mesh

        self.ctx = ctx
        m, tr = ctx.config, ctx.traffic
        self.m = m
        self.hp = m["train"]["optimizer"]
        self.T = tr["seq_len"]
        self.B = tr["per_chip_batch"] * len(ctx.devices)
        self.key = weights.seed_key(ctx.seed)
        tcfg = TransformerConfig(
            vocab_size=m["vocab_size"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"], d_head=m["head_dim"],
            d_ff=m["intermediate_size"], n_layers=m["num_hidden_layers"],
            n_kv_heads=m["num_key_value_heads"],
            attn_window=m.get("sliding_window") or 0,
            rope_theta=m["rope_theta"], compute_dtype=jnp.bfloat16)
        mesh = create_hybrid_mesh(devices=ctx.devices, dp=len(ctx.devices))
        hp = self.hp
        opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                          eps=hp["eps"], weight_decay=hp["weight_decay"])
        self.step, shard_state, shard_batch = make_train_step(mesh, tcfg, opt)

        n_batches = max(tr["resident_batches"], CHECK_STEPS)
        self.batches = []
        make = jax.jit(lambda k, i: weights.lm_tokens(
            k, i, self.B, self.T + 1, m["vocab_size"]))
        for i in range(n_batches):
            toks = make(self.key, i)
            self.batches.append(shard_batch((toks[:, :-1], toks[:, 1:])))

        params = jax.jit(lambda k: weights.lm_params(k, m, jnp.float32))(
            self.key)
        self.params, self.opt_state = shard_state(params, opt.init(params))
        del params
        self.steps_done = 0

        # The first steps of the very object the window drives.
        losses = []
        for i in range(CHECK_STEPS):
            losses.append(self._dispatch())
            if i == 0:
                grads = grad_norms_from_mu(self.opt_state[0].mu, hp["b1"])
        self.program = {
            "losses": [float(x) for x in losses], "grad_norms": grads,
            "delta_norms": delta_norms(self.params, self.key, m)}

    def _dispatch(self):
        batch = self.batches[self.steps_done % len(self.batches)]
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, batch)
        self.steps_done += 1
        return loss

    def window(self, seconds: float):
        jax.block_until_ready(self.params)
        return traincheck.mfu_window(self.ctx, seconds, self._dispatch,
                                     self.B, f"x {self.T} tokens")

    def reference(self, precision: str = "f32") -> Dict:
        """The plain reference over the same first steps."""
        m, hp = self.m, self.hp
        params = jax.jit(lambda k: weights.lm_params(k, m, jnp.float32))(
            self.key)
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        mu, nu = zeros(params), zeros(params)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, y: ref.loss(p, t, y, m, precision)))
        update = jax.jit(lambda p, g, mu, nu, c: ref.adamw_step(
            p, g, mu, nu, c, hp), donate_argnums=(0, 2, 3))
        out = {"losses": []}
        for i in range(CHECK_STEPS):
            toks, targets = self.batches[i % len(self.batches)]
            loss, g = grad(params, toks, targets)
            out["losses"].append(float(loss))
            if i == 0:
                out["grad_norms"] = flat_norms(g)
            params, mu, nu = update(params, g, mu, nu,
                                    jnp.float32(i + 1))
            del g
        out["delta_norms"] = delta_norms(params, self.key, m)
        return out

    def free_program(self) -> None:
        self.params = self.opt_state = None

    def readings(self, control: str = "") -> Dict:
        """What `correct` compares, and with `control` the same numbers
        for the reference computed in that lower precision."""
        self.free_program()
        want, limits = self.reference(), self.m["limits"]
        out = {"program": traincheck.compare(self.program, want, limits)}
        if control:
            out["control"] = traincheck.compare(self.reference(control),
                                                want, limits)
        return out

    def check(self):
        return self.readings()["program"]
