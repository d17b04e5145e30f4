"""A decoder of gated short-convolution and softmax layers with routed
experts (`family: conv_moe_lm`) trained through `make_train_step`
(models/transformer.py -> models/pattern.py) with AdamW, bf16 compute over
f32 parameters, on resident batches.

The model is built through `TransformerConfig` and `make_train_step` only.
`train_mfu`'s numerator is `lib/counts_conv_moe.py`'s: this runner does
NOT call `traincheck.mfu_window`, which ends in
`counts.train_flops_per_sample` and so counts every family but `resnet`
as a uniform dense attention decoder.  A next training family does the
same: `traincheck.timed_steps` and `traincheck.compare` are general;
count in a module of your own.
"""
from __future__ import annotations

import statistics
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.lib import counts_conv_moe as counts
from benchmark.lib import traincheck, weights
from benchmark.lib import weights_conv_moe as W
from benchmark.lib.harness import WindowResult
from benchmark.reference import conv_moe as ref
from benchmark.runners.lm_train import flat_norms, grad_norms_from_mu

CHECK_STEPS = 3


def transformer_config(m: Dict, dtype):
    """The program's configuration of `m` (a configuration file's keys)."""
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.transformer import AttnSpec, ConvSpec, Rotary
    ks = W.kinds(m)
    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], d_head=m["head_dim"],
        d_ff=m["intermediate_size"], n_layers=m["num_hidden_layers"],
        n_kv_heads=m["num_key_value_heads"], compute_dtype=dtype,
        layer_attn=tuple(k[0] for k in ks),
        layer_mlp=tuple(k[1] for k in ks),
        attn_specs=(
            ("conv", ConvSpec(taps=m["conv_L_cache"])),
            ("full_attention", AttnSpec(
                m["num_attention_heads"],
                rotary=Rotary(theta=float(m["rope_theta"])),
                qk_norm=True))),
        n_experts=W.router_width(m),
        experts_per_token=m["num_experts_per_tok"],
        expert_ff=m["moe_intermediate_size"],
        routed_scale=float(m["routed_scaling_factor"]),
        experts_held=W.held(m), expert_bias=bool(m["use_expert_bias"]),
        route_eps=ref.ROUTE_EPS)


@jax.jit
def _gap(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def _at(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree


class Runner:
    def __init__(self, ctx):
        from horovod_tpu.models import make_train_step
        from horovod_tpu.parallel import create_hybrid_mesh

        self.ctx = ctx
        m, tr = ctx.config, ctx.traffic
        self.m = m
        self.hp = hp = m["train"]["optimizer"]
        self.T = tr["seq_len"]
        self.B = tr["per_chip_batch"] * len(ctx.devices)
        self.key = weights.seed_key(ctx.seed)
        tcfg = transformer_config(m, jnp.bfloat16)
        mesh = create_hybrid_mesh(devices=ctx.devices, dp=len(ctx.devices))
        opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                          eps=hp["eps"], weight_decay=hp["weight_decay"])
        self.step, shard_state, shard_batch = make_train_step(mesh, tcfg,
                                                              opt)

        n_batches = max(tr["resident_batches"], CHECK_STEPS)
        make = jax.jit(lambda k, i: weights.lm_tokens(
            k, i, self.B, self.T + 1, m["vocab_size"]))
        self.batches = []
        for i in range(n_batches):
            toks = make(self.key, i)
            self.batches.append(shard_batch((toks[:, :-1], toks[:, 1:])))

        params = jax.jit(lambda k: W.params(k, m, jnp.float32))(self.key)
        self.params, self.opt_state = shard_state(params, opt.init(params))
        del params
        self.steps_done = 0
        self.routed = []            # a step's counts, left on the device
        self._draw = {}             # a leaf's initial values, by path

        # The first steps of the very object the window drives.
        losses = []
        for i in range(CHECK_STEPS):
            losses.append(self._dispatch())
            if i == 0:
                grads = grad_norms_from_mu(self.opt_state[0].mu, hp["b1"])
        self.program = {
            "losses": [float(x) for x in losses], "grad_norms": grads,
            "delta_norms": self.delta_norms(self.params)}

    def delta_norms(self, params: Dict) -> Dict[str, float]:
        """Norm of (params - the seed's initial weights) by leaf; the
        initial weights are drawn again a leaf at a time, never held
        whole."""
        out = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            name = jax.tree_util.keystr(path)
            if name not in self._draw:
                self._draw[name] = jax.jit(lambda k, path=path: _at(
                    W.params(k, self.m, jnp.float32), path))
            out[name] = float(_gap(leaf, self._draw[name](self.key)))
        return out

    def _dispatch(self):
        batch = self.batches[self.steps_done % len(self.batches)]
        self.params, self.opt_state, loss, routed = self.step(
            self.params, self.opt_state, batch)
        self.routed.append(routed)
        self.steps_done += 1
        return loss

    def window(self, seconds: float):
        jax.block_until_ready(self.params)
        self.routed = []
        steps, secs, ends = traincheck.timed_steps(
            self.ctx, seconds, self._dispatch, jax.block_until_ready)
        flops = counts.train_flops_per_sample(self.m, self.ctx.traffic)
        mfu = 100.0 * flops * self.B * steps / secs / (
            len(self.ctx.devices) * self.ctx.peaks["bf16_flops_per_s"])
        step_ms = [1e3 * (b - a) for a, b in zip(ends, ends[1:])]
        # [steps, sparse layers, (experts_hit, expert_load_max, pairs_here)]
        routed = np.stack([np.asarray(r) for r in self.routed]).astype(
            np.int64)
        layer_steps = routed.shape[0] * routed.shape[1]
        pairs = self.B * self.T * self.m["num_experts_per_tok"]
        counters = {
            "moe_layer_steps": float(layer_steps),
            "experts_hit_sum": float(routed[..., 0].sum()),
            "expert_load_max_sum": float(routed[..., 1].sum()),
            "pairs_here_sum": float(routed[..., 2].sum()),
            "pairs_sum": float(pairs * layer_steps)}
        print(f"window: {steps} steps of {self.B} x {self.T} tokens in "
              f"{secs:.3f} s, {self.B * steps / secs:.2f} samples/s, "
              f"median step {statistics.median(step_ms):.3f} ms; of a "
              f"sparse layer's {pairs} pairs "
              f"{counters['pairs_here_sum'] / layer_steps:.0f} lay in a "
              f"group here, the fullest expert took "
              f"{counters['expert_load_max_sum'] / layer_steps:.0f}")
        return WindowResult(attempted=steps, failed=0,
                            end_to_end={"train_mfu": mfu},
                            counters=counters,
                            samples={"step_ms": step_ms})

    def reference(self, precision: str = "f32", **how) -> Dict:
        """The plain reference over the same first steps: a sequence's
        loss and gradient at a time, added up."""
        m, hp = self.m, self.hp
        params = jax.jit(lambda k: W.params(k, m, jnp.float32))(self.key)
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        mu, nu = zeros(params), zeros(params)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, y: ref.sequence_loss(p, t, y, m, precision,
                                              **how)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                      donate_argnums=(0,))
        mean = jax.jit(lambda g, n: jax.tree_util.tree_map(
            lambda x: x / n, g), donate_argnums=(0,))
        update = jax.jit(lambda p, g, mu, nu, c: ref.adamw_step(
            p, g, mu, nu, c, hp), donate_argnums=(0, 2, 3))
        out = {"losses": []}
        for i in range(CHECK_STEPS):
            toks, targets = self.batches[i % len(self.batches)]
            total, g = 0.0, None
            for b in range(toks.shape[0]):
                l, gb = grad(params, toks[b], targets[b])
                total += float(l)
                g = gb if g is None else add(g, gb)
                del gb
            g = mean(g, jnp.float32(toks.size))
            out["losses"].append(total / toks.size)
            if i == 0:
                out["grad_norms"] = flat_norms(g)
            params, mu, nu = update(params, g, mu, nu, jnp.float32(i + 1))
            del g
        out["delta_norms"] = self.delta_norms(params)
        return out

    def free_program(self) -> None:
        self.params = self.opt_state = None
        self.routed = []

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
