"""Multi-slice (hierarchical) data parallelism example.

Reference: NCCLHierarchicalAllreduce (ops/nccl_operations.cc) — the
two-tier reduce for two-tier networks.  On a TPU multipod: `dcn` slices
over the data-center network, chips within a slice over ICI; gradients
reduce-scatter over ICI, allreduce over DCN on 1/ici_size of the bytes,
then all-gather over ICI.

Runs on the 8-device CPU sim (2 virtual slices x 4 chips):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/hierarchical_multislice.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import create_hierarchical_mesh
from horovod_tpu.parallel.hierarchical import hierarchical_allreduce


def main():
    hvd.init()
    n = len(jax.devices())
    assert n >= 4 and n % 2 == 0, f"need >=4 even devices, have {n}"
    mesh = create_hierarchical_mesh(dcn=2, ici=n // 2)
    print(f"mesh: {dict(mesh.shape)}")

    params = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    bspec = P(("dcn", hvd.GLOBAL_AXIS))

    def step(params, opt_state, batch):
        x, y = batch

        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # ICI reduce-scatter -> DCN allreduce -> ICI all-gather, fused
        # across the gradient tree.
        grads = hierarchical_allreduce(grads, "dcn")
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    sm = shard_map(step, mesh=mesh,
                   in_specs=(P(), P(), (bspec, bspec)),
                   out_specs=(P(), P(), P()), check_vma=False)
    compiled = jax.jit(sm)

    rng = np.random.RandomState(0)
    w_true = np.asarray([1.0, -2.0, 3.0, 0.5], np.float32)
    for i in range(30):
        x = rng.randn(n * 4, 4).astype(np.float32)
        y = x @ w_true + 0.7
        batch = jax.device_put((x, y), NamedSharding(mesh, bspec))
        params, opt_state, loss = compiled(params, opt_state, batch)
    print(f"final loss {float(loss):.5f}; "
          f"w={np.asarray(params['w']).round(2)} (true {w_true})")
    assert float(loss) < 0.05


if __name__ == "__main__":
    main()
