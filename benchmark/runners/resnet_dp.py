"""ResNet-50 trained the way the reference's synthetic benchmark does,
through this repo's Horovod surface: `hvd.init` -> `DistributedOptimizer`
-> `broadcast_parameters` -> `data_parallel` -> `shard_batch`, one process
over the chips of the host, bf16 compute.  The step is built as `bench.py`
and `chip_smoke.py` build it, with one departure from them and from the
source, listed under `reduced` in the configuration: batch norm takes its
statistics over the global batch (`axis_name=hvd.GLOBAL_AXIS`), where
they normalise each rank's images alone."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.lib import traincheck, weights
from benchmark.lib.harness import Check
from benchmark.reference import resnet as ref
from benchmark.runners.lm_train import CHECK_STEPS, flat_norms


def _momentum_traces(opt_state, params) -> Dict[str, float]:
    """Norms of the momentum trace, leaf by leaf.  After the first step
    of optax.sgd the trace is the gradient the optimizer was handed."""
    found = []

    def walk(node):
        if hasattr(node, "trace"):
            found.append(node.trace)
        elif isinstance(node, (tuple, list)):     # NamedTuples too
            for x in node:
                walk(x)

    walk(getattr(opt_state, "inner", opt_state))
    if len(found) != 1 or (jax.tree_util.tree_structure(found[0])
                           != jax.tree_util.tree_structure(params)):
        raise RuntimeError("cannot find the momentum trace in the "
                           "optimizer's state")
    return flat_norms(found[0])


class Runner:
    def __init__(self, ctx):
        import horovod_tpu as hvd
        from horovod_tpu.models import resnet_apply

        self.ctx, self.hvd = ctx, hvd
        m, tr = ctx.config, ctx.traffic
        self.m = m
        hvd.init()
        n = hvd.size()
        if n != len(ctx.devices):
            raise RuntimeError(f"hvd.size() {n} != chips {len(ctx.devices)}")
        self.B = tr["per_chip_batch"] * n
        self.key = weights.seed_key(ctx.seed)
        self.hp = dict(m["train"]["optimizer"])
        self.hp["learning_rate"] = self.hp["learning_rate_per_chip"] * n
        classes = m["num_classes"]
        prog_cfg = {"depth": m["depth"], "bottleneck": True,
                    "sizes": weights.RESNET_STAGES[m["depth"]]}

        params, stats = jax.jit(
            lambda k: weights.resnet_variables(k, m))(self.key)
        opt = hvd.DistributedOptimizer(optax.sgd(
            self.hp["learning_rate"], momentum=self.hp["momentum"]))
        opt_state = opt.init(params)
        params = hvd.broadcast_parameters(params, root_rank=0)
        self.state = {"params": params, "batch_stats": stats}
        self.opt_state = opt_state

        @hvd.data_parallel
        def step(state, opt_state, batch):
            xb, yb = batch

            def loss_fn(p):
                logits, ns = resnet_apply(
                    {"params": p, "batch_stats": state["batch_stats"],
                     "config": prog_cfg},
                    xb, train=True, compute_dtype=jnp.bfloat16,
                    axis_name=hvd.GLOBAL_AXIS)
                onehot = jax.nn.one_hot(yb, classes)
                return -jnp.mean(jnp.sum(
                    jax.nn.log_softmax(logits) * onehot, -1)), ns

            (loss, ns), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"])
            updates, opt_state2 = opt.update(grads, opt_state,
                                             state["params"])
            new = optax.apply_updates(state["params"], updates)
            # the per-rank loss is the mean over that rank's rows
            loss = jax.lax.pmean(loss, hvd.GLOBAL_AXIS)
            return {"params": new, "batch_stats": ns}, opt_state2, loss

        self.step = step
        self.make = jax.jit(lambda k, i: weights.images(
            k, i, self.B, m["image_size"], classes))
        # Every batch is resident, sharded over the chips; the reference
        # makes its own copies of the first few again from the seed.
        self.batches = [
            hvd.shard_batch(self.make(self.key, i))
            for i in range(max(tr["resident_batches"], CHECK_STEPS))]
        self.steps_done = 0

        losses = []
        for i in range(CHECK_STEPS):
            losses.append(self._dispatch())
            if i == 0:
                grads = _momentum_traces(self.opt_state,
                                         self.state["params"])
        p0, _ = jax.jit(lambda k: weights.resnet_variables(k, m))(self.key)
        delta = flat_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b))(self.state["params"], p0))
        self.program = {"losses": [float(x) for x in losses],
                        "grad_norms": grads, "delta_norms": delta}

    def _dispatch(self):
        batch = self.batches[self.steps_done % len(self.batches)]
        self.state, self.opt_state, loss = self.step(
            self.state, self.opt_state, batch)
        self.steps_done += 1
        return loss

    def window(self, seconds: float):
        jax.block_until_ready(self.state)
        return traincheck.mfu_window(self.ctx, seconds, self._dispatch,
                                     self.B, "images")

    def replicas_differ(self) -> int:
        """Leaves whose copies on the chips are not bitwise equal."""
        bad = 0
        for leaf in jax.tree_util.tree_leaves(self.state["params"]):
            first, *rest = (np.asarray(s.data)
                            for s in leaf.addressable_shards)
            bad += any(first.tobytes() != r.tobytes() for r in rest)
        return bad

    def reference(self, precision: str = "f32") -> Dict:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        m, hp = self.m, self.hp
        mesh = Mesh(np.array(self.ctx.devices), ("b",))
        rows, rep = NamedSharding(mesh, P("b")), NamedSharding(mesh, P())
        params, _ = jax.jit(lambda k: weights.resnet_variables(k, m),
                            out_shardings=rep)(self.key)
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        p0 = params
        grad = jax.jit(jax.value_and_grad(
            lambda p, x, y: ref.loss(p, x, y, m, precision)))
        update = jax.jit(lambda p, g, t: ref.sgd_step(p, g, t, hp))
        out = {"losses": []}
        for i in range(CHECK_STEPS):
            x, y = (jax.device_put(a, rows)
                    for a in self.make(self.key, i))
            loss, g = grad(params, x, y)
            out["losses"].append(float(loss))
            if i == 0:
                out["grad_norms"] = flat_norms(g)
            params, trace = update(params, g, trace)
        out["delta_norms"] = flat_norms(jax.tree_util.tree_map(
            jnp.subtract, params, p0))
        return out

    def free_program(self) -> None:
        self.state = self.opt_state = self.batches = None

    def readings(self, control: str = "") -> Dict:
        differ = self.replicas_differ()
        self.free_program()
        want, limits = self.reference(), self.m["limits"]
        out = {"program": traincheck.compare(self.program, want, limits)}
        out["program"].append(Check(
            "parameter leaves whose copies differ between chips after the "
            "window", differ, 0))
        if control:
            out["control"] = traincheck.compare(self.reference(control),
                                                want, limits)
        return out

    def check(self):
        return self.readings()["program"]
