"""Worker main for the REAL two-tier (cross-process "dcn" x in-process
"hvd") hierarchical collective test.

Launched by `exec_run` with -np 2: each process forces FOUR virtual CPU
devices, so the 2x4 hierarchical mesh's "dcn" axis lands exactly on the
process boundary — its collectives cross the gloo transport like real
DCN hops, while the inner "hvd" axis stays process-local like ICI.  The
single-process suites only ever fold both tiers into one host; this is
the only place the slow-tier leg actually leaves the process.

Asserted against a flat (single-level) reference on the same mesh:
  - exact hierarchical allreduce == flat allreduce bitwise on
    integer-valued f32 (any summation order is exact);
  - int8 DCN-wire hierarchical allreduce stays close (quantized leg
    engaged: error must be nonzero, bounded);
  - hierarchical_reduce_scatter + hierarchical_all_gather reassembles
    the exact flat sum bitwise (pins dcn-major segment ownership across
    a REAL process boundary).

Results go to $HVD_TEST_OUT/rank{process_index}.json.
"""

import json
import os
import sys

# FOUR local virtual devices per process — before any jax import.  The
# parent test process carries the conftest's count=8 flag; override, do
# not append.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.parallel import hierarchical  # noqa: E402
from horovod_tpu.parallel.mesh import create_hierarchical_mesh  # noqa: E402

DCN, ICI = 2, 4
W = 64  # payload width (divisible by DCN*ICI: exercises no-pad RS path)


def main():
    hvd.init()
    assert jax.process_count() == DCN, jax.process_count()
    assert jax.local_device_count() == ICI, jax.local_device_count()
    assert hvd.size() == DCN * ICI

    pidx = jax.process_index()
    mesh = create_hierarchical_mesh(DCN, ICI, devices=jax.devices())
    spec = P(("dcn", hvd.GLOBAL_AXIS))
    sharding = NamedSharding(mesh, spec)

    # Same seed on both processes: row r is global rank r's contribution.
    rng = np.random.RandomState(0)
    data = np.round(rng.randn(DCN * ICI, W) * 4).astype(np.float32)
    garr = jax.make_array_from_callback(
        data.shape, sharding, lambda idx: data[idx])

    def run(fn):
        sm = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=P(),
                       check_vma=False)
        return np.asarray(jax.jit(sm)(garr))

    def flat(x):
        return hvd.allreduce(x[0], op=hvd.Average,
                             axis_name=("dcn", hvd.GLOBAL_AXIS))

    def hier_exact(x):
        out = hierarchical.hierarchical_allreduce(
            {"g": x[0]}, "dcn", hvd.GLOBAL_AXIS, average=True)
        return out["g"]

    def hier_int8(x):
        out = hierarchical.hierarchical_allreduce(
            {"g": x[0]}, "dcn", hvd.GLOBAL_AXIS, average=True,
            dcn_wire="int8")
        return out["g"]

    def rs_ag(x):
        shard = hierarchical.hierarchical_reduce_scatter(
            x[0], "dcn", hvd.GLOBAL_AXIS)
        return hierarchical.hierarchical_all_gather(
            shard, "dcn", hvd.GLOBAL_AXIS)

    ref = run(flat)
    exact = run(hier_exact)
    quant = run(hier_int8)
    roundtrip = run(rs_ag)
    flat_sum = np.sum(data, axis=0)

    results = {
        "rank": pidx,
        "size": hvd.size(),
        "hier_exact_bitwise": bool((exact == ref).all()),
        "int8_err": float(np.abs(quant - ref).max()),
        "ref_scale": float(np.abs(ref).max()),
        "rs_ag_bitwise": bool((roundtrip == flat_sum).all()),
    }
    out_dir = os.environ["HVD_TEST_OUT"]
    with open(os.path.join(out_dir, f"rank{pidx}.json"), "w") as f:
        json.dump(results, f)

    hvd.shutdown()


if __name__ == "__main__":
    main()
