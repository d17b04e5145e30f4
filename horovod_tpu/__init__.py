"""horovod_tpu — a TPU-native distributed training framework with the
capability surface of Horovod (reference: nateagr/horovod, a fork of
horovod/horovod; see SURVEY.md).

Design: SPMD over a `jax.sharding.Mesh` instead of an eager negotiation
runtime.  Collectives are XLA programs over TPU ICI; the coordination
thread, tensor queue, fusion buffer, and response cache of the reference
become trace/compile-time constructs (see SURVEY.md §7).

Canonical usage mirrors `import horovod.torch as hvd`:

    import horovod_tpu as hvd
    hvd.init()
    ...
    grads = hvd.allreduce(grads)           # eager, or inside jit
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
"""

from .version import __version__

from .common.basics import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    size,
    rank,
    local_size,
    local_rank,
    cross_size,
    cross_rank,
    process_index,
    num_processes,
    local_device_ranks,
    is_homogeneous,
    global_mesh,
    global_devices,
    tpu_built,
    xla_built,
    mpi_built,
    nccl_built,
    gloo_built,
    ccl_built,
    cuda_built,
    rocm_built,
    ddl_built,
    mpi_enabled,
    gloo_enabled,
    global_process_set,
    mpi_threads_supported,
    add_process_set,
    remove_process_set,
    get_process_set,
    ProcessSet,
    GLOBAL_AXIS,
)

from .common.exceptions import (  # noqa: F401
    HorovodTpuError,
    HorovodInternalError,
    HostsUpdatedInterrupt,
)

from .ops.collectives import (  # noqa: F401
    Average,
    Sum,
    Min,
    Max,
    Product,
    Adasum,
    PerRank,
    allreduce,
    allreduce_async,
    grouped_allreduce,
    grouped_allreduce_async,
    allgather,
    allgather_async,
    grouped_allgather,
    broadcast,
    broadcast_async,
    alltoall,
    alltoall_async,
    reducescatter,
    reducescatter_async,
    grouped_reducescatter,
    barrier,
    join,
    join_mode,
    joined_ranks,
    poll,
    synchronize,
)

from .ops.compression import Compression  # noqa: F401

from .ops.wire import (  # noqa: F401
    WireCodec,
    WirePolicy,
    get_codec,
    parse_wire_policy,
    wire_names,
)

from .ops.functions import (  # noqa: F401
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_object,
    allgather_object,
)

from .parallel.optimizer import (  # noqa: F401
    DistributedOptimizer,
    DistributedGradientTransformation,
    grad_accum_bytes,
    optimizer_state_bytes,
    sharded_state_specs,
)

from .parallel.zero3 import (  # noqa: F401
    ZeroParamPlacement,
    zero3_placement,
)

from .parallel.data_parallel import (  # noqa: F401
    allreduce_gradients,
    data_parallel,
    distributed_grad,
    DistributedGradientTape,
    error_feedback_init,
    fused_pipeline_plan,
    gradient_bucket_partition,
    shard_batch,
    wire_policy_plan,
)

from .utils.timeline import (  # noqa: F401
    start_timeline,
    stop_timeline,
)

from .utils.prefetch import (  # noqa: F401
    prefetch_to_device,
    BackgroundPrefetcher,
)

from .utils.autotune import (  # noqa: F401
    ParameterManager,
    get_manager as autotune_manager,
)


def autotune_record_step(items: float = 1.0) -> None:
    """Feed the autotuner one training step of `items` samples/tokens
    (no-op unless HOROVOD_AUTOTUNE=1).  Reference: parameter_manager.cc
    Update() driven by the background loop's tensor throughput."""
    from .utils import autotune as _at
    mgr = _at.get_manager()
    if mgr is not None:
        mgr.record_step(items)

from .parallel.hierarchical import (  # noqa: F401
    dcn_shard_size,
    hierarchical_all_gather,
    hierarchical_allreduce,
    hierarchical_error_feedback_init,
    hierarchical_reduce_scatter,
)

from . import callbacks  # noqa: F401
from . import elastic  # noqa: F401
from . import guard  # noqa: F401
from . import metrics  # noqa: F401

from .guard import (  # noqa: F401
    DynamicLossScale,
    GuardState,
    TrainingGuard,
)
