"""Worker main for the REAL two-process training-health-guardian test.

Launched by `exec_run` with -np 2 (one CPU device per process, gloo
cross-process collectives — the same harness as multiproc_main.py).
Drives the full escalation ladder of docs/GUARD.md end to end:

Phase A (coordinated skip-step): at step 3 rank 1 ALONE arms
`guard.nan_grad` — its batch shard is poisoned, its local gradients go
non-finite, and the fused sentinel's cross-rank OR must make BOTH ranks
skip the same step and decay the same loss scale, with no divergence.

Phase B (divergence -> rollback): at step 6 rank 1 ALONE arms
`guard.param_bitflip` — one mantissa bit of its replica flips.  Every
gradient stays finite, so only the periodic digest check (interval 4,
step 8) can catch it; the verdict escalates and both ranks restore the
step-4 digest-verified checkpoint and resume.

Both ranks must finish with bitwise-identical parameters.  Per-step
loss-scale / flag traces and the final params go to
$HVD_TEST_OUT/rank{r}.json.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import faults  # noqa: E402

shard_map = jax.shard_map  # noqa: E402 (compat alias from the hvd import)

LOCAL_B = 4     # batch rows per rank
DIM = 4
NAN_STEP = 3    # rank 1 poisons its batch here (phase A)
FLIP_STEP = 6   # rank 1 flips a param bit here (phase B)
CKPT_STEP = 4   # digest-verified baseline the rollback restores
DIGEST_INTERVAL = 4
N_STEPS = 12


def _make_global(local_tree, mesh):
    """Lift each rank's LOCAL host rows into a dim0-sharded global
    array (the injection must land in this rank's own shard, so the
    usual same-seed global-batch path does not apply)."""
    def mk(leaf):
        leaf = np.asarray(leaf)
        gshape = (leaf.shape[0] * hvd.size(),) + leaf.shape[1:]
        sharding = NamedSharding(mesh, P(hvd.GLOBAL_AXIS))
        return jax.make_array_from_callback(
            gshape, sharding, lambda idx: leaf)
    return jax.tree_util.tree_map(mk, local_tree)


def main():
    hvd.init()
    rank, n = hvd.rank(), hvd.size()
    assert n == 2 and jax.process_count() == 2
    mesh = hvd.global_mesh()

    scaler = hvd.DynamicLossScale(init_scale=1024.0,
                                  growth_interval=1000)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), guard=scaler)
    ckpt_dir = os.path.join(os.environ["HVD_TEST_OUT"], "guard_ckpt")
    guard = hvd.TrainingGuard(
        scaler=scaler, checkpoint_dir=ckpt_dir,
        digest_interval=DIGEST_INTERVAL, max_nonfinite=3)

    # Same seed on both ranks; each keeps only its own rows host-side so
    # maybe_inject can poison them before they are lifted to the mesh.
    rng = np.random.RandomState(0)
    true_w = rng.uniform(size=(DIM,)).astype(np.float32)
    xs = rng.uniform(size=(n * LOCAL_B, DIM)).astype(np.float32)
    ys = (xs @ true_w).astype(np.float32)
    x_local = xs[rank * LOCAL_B:(rank + 1) * LOCAL_B]
    y_local = ys[rank * LOCAL_B:(rank + 1) * LOCAL_B]

    def loss_fn(w, x, y, scale):
        return jnp.mean((x @ w - y) ** 2) * scale

    def step(w, opt_state, x, y):
        scale = opt_state.guard.loss_scale
        grads = jax.grad(loss_fn)(w, x, y, scale)
        updates, opt_state = opt.update(grads, opt_state, w)
        return optax.apply_updates(w, updates), opt_state

    sm = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(hvd.GLOBAL_AXIS), P(hvd.GLOBAL_AXIS)),
        out_specs=(P(), P()),
        check_vma=False)
    compiled = jax.jit(sm)

    w = jnp.zeros((DIM,), jnp.float32)
    opt_state = opt.init(w)

    trace = []
    rollback_at = None
    mismatch_bucket = None
    for t in range(1, N_STEPS + 1):
        if rank == 1 and t == NAN_STEP:
            faults.install("guard.nan_grad@1:err")
        if rank == 1 and t == FLIP_STEP:
            faults.install("guard.param_bitflip@1:err")
        batch, w = guard.maybe_inject(
            {"x": x_local, "y": y_local}, w)
        faults.clear()  # exactly one armed firing per phase
        # Host-normalize the params on BOTH ranks: a rank-local
        # injected array must not give the jitted step per-rank input
        # shardings (divergence is carried by the VALUES).
        w = np.asarray(w)
        gbatch = _make_global(batch, mesh)
        w, opt_state = compiled(w, opt_state, gbatch["x"], gbatch["y"])
        v = guard.observe(opt_state, w, t)
        trace.append({"step": t, "flagged": v.flagged,
                      "scale": v.loss_scale,
                      "nonfinite": v.nonfinite_steps})
        if v.rollback:
            rollback_at = t
            mismatch_bucket = v.mismatch_bucket
            restored = guard.rollback({"w": w, "opt": opt_state})
            assert restored is not None
            w = restored["w"]
            opt_state = guard.reset_guard_state(restored["opt"], scaler)
        elif t == CKPT_STEP:
            assert guard.checkpoint(t, {"w": w, "opt": opt_state})

    final_ok = guard._check_digests(w) is None

    results = {
        "rank": rank,
        "size": n,
        "trace": trace,
        "rollback_at": rollback_at,
        "mismatch_bucket": mismatch_bucket,
        "generation": guard.generation,
        "last_verified_step": guard.last_verified_step,
        "final_digest_clean": final_ok,
        "final_w": np.asarray(w).tolist(),
    }
    out_dir = os.environ["HVD_TEST_OUT"]
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump(results, f)

    hvd.shutdown()


if __name__ == "__main__":
    main()
