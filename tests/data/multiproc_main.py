"""Worker main for REAL cross-process collective integration tests.

Launched by `exec_run` with -np 2 under JAX_PLATFORMS=cpu: each process
bootstraps `jax.distributed` through `hvd.init()` (coordinator env comes
from the launcher), and runs actual cross-process collectives — the
TPU-native analog of the reference's `horovodrun -np 2 pytest` pattern
(SURVEY.md §4).  Results are written to $HVD_TEST_OUT/rank{r}.json for the
test to assert.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    hvd.init()
    rank, n = hvd.rank(), hvd.size()
    assert n == int(os.environ["HOROVOD_SIZE"]), (n, os.environ["HOROVOD_SIZE"])
    assert jax.process_count() == n, "jax.distributed did not bootstrap"

    results = {"rank": rank, "size": n}

    # allreduce: sum of rank-dependent contributions.
    out = hvd.allreduce(jnp.array([1.0, 2.0]) * (rank + 1), op=hvd.Sum)
    results["allreduce_sum"] = np.asarray(out).tolist()

    # average round-trips the mean.
    out = hvd.allreduce(jnp.full((3,), float(rank)), op=hvd.Average)
    results["allreduce_avg"] = np.asarray(out).tolist()

    # broadcast: everyone gets root's value.
    out = hvd.broadcast(jnp.array([100.0 + rank]), root_rank=0)
    results["broadcast"] = np.asarray(out).tolist()

    # allgather: first-dim concat in rank order.
    out = hvd.allgather(jnp.full((1, 2), float(rank)))
    results["allgather"] = np.asarray(out).tolist()

    # Ragged allgather: rank r contributes r+1 rows.
    out = hvd.allgather(jnp.full((rank + 1, 1), float(rank)))
    results["allgather_ragged"] = np.asarray(out).ravel().tolist()

    # alltoall: rank r receives chunk r from every sender s (= value s).
    out = hvd.alltoall(jnp.full((n,), float(rank)))
    results["alltoall"] = np.asarray(out).tolist()

    # reducescatter: each rank receives its row of the summed tensor.
    out = hvd.reducescatter(jnp.full((2 * n,), float(rank + 1)), op=hvd.Sum)
    results["reducescatter"] = np.asarray(out).tolist()

    # Concurrent process sets across processes (reference: process_set.cc
    # — collectives on disjoint subsets run concurrently): evens and odds
    # each reduce within their own set.
    evens = hvd.add_process_set(list(range(0, n, 2)))
    odds = hvd.add_process_set(list(range(1, n, 2)))
    mine = evens if rank % 2 == 0 else odds
    out = hvd.allreduce(jnp.array([float(rank + 1)]), op=hvd.Sum,
                        process_set=mine)
    results["ps_sum"] = np.asarray(out).tolist()

    # data_parallel across processes: shard_batch takes THIS process's
    # rows (every rank feeds its own batch) and the compiled step's
    # allreduce averages over every rank.
    step = hvd.data_parallel(
        lambda w, s, batch: (w, s, hvd.allreduce(jnp.mean(batch) + w)))
    batch = hvd.shard_batch(jnp.full((2, 3), float(rank)))
    assert batch.shape == (2 * n, 3), batch.shape
    *_, mean = step(jnp.zeros(()), jnp.zeros(()), batch)
    results["data_parallel_mean"] = float(np.asarray(mean))

    out_dir = os.environ["HVD_TEST_OUT"]

    # Durable checkpoint under jax.distributed: rank 0 writes the host
    # snapshot; restore broadcasts so every rank gets rank 0's state.
    from horovod_tpu.utils import checkpoint as ckpt_mod
    mgr = ckpt_mod.CheckpointManager(os.path.join(out_dir, "ckpt"))
    wrote = mgr.save(1, {"w": jnp.full((3,), 1.0 + rank)})
    assert wrote == (rank == 0)
    restored = mgr.restore_latest()
    results["ckpt"] = np.asarray(restored["w"]).tolist()
    # latest_step is collectively safe (rank-0 view broadcast).
    results["ckpt_latest"] = mgr.latest_step()

    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump(results, f)

    hvd.shutdown()


if __name__ == "__main__":
    main()
