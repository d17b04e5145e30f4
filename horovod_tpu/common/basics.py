"""Core runtime state: initialization, ranks, the device mesh, process sets.

Reference parity map (SURVEY.md §2.1):
  - horovod/common/operations.cc `horovod_init` / `horovod_shutdown` /
    `horovod_rank` / `horovod_size` ...      → `init()` / `shutdown()` / ...
  - horovod/common/global_state.h `HorovodGlobalState` → `_GlobalState`
  - horovod/common/process_set.cc `ProcessSetTable` → `ProcessSetTable`

TPU-native redesign: Horovod spawns a background coordination thread because
GPU workers execute eagerly and must *negotiate* which tensors are ready on
every rank.  Under XLA SPMD there is nothing to negotiate: collectives are
compiled into the program and scheduled over ICI by the compiler.  What
remains runtime state is exactly what this module holds — process bootstrap
(`jax.distributed`), the global `jax.sharding.Mesh`, and the process-set
table (sub-meshes).

Rank model: **one rank per chip** (Horovod: one rank per GPU).  A controller
process drives `local_size()` ranks — its local devices.  `rank()` returns
the global index of this process's first device, which preserves the
"``if hvd.rank() == 0``" idiom (process 0 owns device-rank 0 in JAX's
device order).
"""

from __future__ import annotations

import atexit
import dataclasses
import logging
import os
import threading
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from . import util
from .exceptions import HorovodTpuError, NotInitializedError

logger = logging.getLogger("horovod_tpu")

# The single mesh axis every data-parallel collective runs over.  Matches
# Horovod's single global communicator (MPI_COMM_WORLD analog).
GLOBAL_AXIS = "hvd"

# Name of the registered global process set (reference: process_set.cc's
# implicit global set with id 0).
GLOBAL_PROCESS_SET_NAME = "global"


@dataclasses.dataclass
class ProcessSet:
    """A subset of ranks with its own sub-mesh.

    Reference: horovod/common/process_set.cc `ProcessSet` — each set gets its
    own controller + communicator; here each set gets its own `Mesh` built
    over the subset's devices, so collectives on different sets can run
    concurrently (XLA schedules them independently).
    """

    ranks: List[int]
    process_set_id: int = -1
    mesh: Optional[Mesh] = None

    def size(self) -> int:
        return len(self.ranks)

    def rank(self) -> int:
        """This process's first-device rank *within* the set."""
        st = _state()
        for local in st.local_device_ranks:
            if local in self.ranks:
                return self.ranks.index(local)
        raise HorovodTpuError(
            f"process set {self.process_set_id} does not include this process"
        )

    def included(self) -> bool:
        st = _state()
        return any(r in self.ranks for r in st.local_device_ranks)

    def __repr__(self):
        return f"ProcessSet(id={self.process_set_id}, ranks={self.ranks})"


class ProcessSetTable:
    """Registry of process sets; id 0 is always the global set."""

    def __init__(self, global_set: ProcessSet):
        self._lock = threading.Lock()
        global_set.process_set_id = 0
        self._sets: Dict[int, ProcessSet] = {0: global_set}
        self._next_id = 1

    def add(self, ps: ProcessSet) -> int:
        with self._lock:
            for existing in self._sets.values():
                if existing.ranks == ps.ranks:
                    raise HorovodTpuError(
                        f"A process set with ranks {ps.ranks} already exists "
                        f"(id={existing.process_set_id})"
                    )
            ps.process_set_id = self._next_id
            self._next_id += 1
            self._sets[ps.process_set_id] = ps
            return ps.process_set_id

    def remove(self, ps_id: int) -> None:
        with self._lock:
            if ps_id == 0:
                raise HorovodTpuError("Cannot remove the global process set")
            self._sets.pop(ps_id)

    def get(self, ps_id: int) -> ProcessSet:
        with self._lock:
            try:
                return self._sets[ps_id]
            except KeyError:
                raise HorovodTpuError(f"Unknown process set id {ps_id}") from None

    def all_sets(self) -> List[ProcessSet]:
        with self._lock:
            return list(self._sets.values())


class _GlobalState:
    """All runtime state (reference: global_state.h `HorovodGlobalState`)."""

    def __init__(self, mesh: Mesh, devices: Sequence[jax.Device]):
        self.mesh = mesh
        self.devices = list(devices)
        self.size = len(self.devices)
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        # Global ranks of this process's devices.
        self.local_device_ranks = [
            i for i, d in enumerate(self.devices)
            if d.process_index == self.process_index
        ]
        self.local_size = len(self.local_device_ranks)
        global_set = ProcessSet(ranks=list(range(self.size)), mesh=mesh)
        self.process_set_table = ProcessSetTable(global_set)
        self.elastic_enabled = False


_global_state: Optional[_GlobalState] = None
_init_lock = threading.Lock()
# True while this process holds a live jax.distributed client (multi-host
# bootstrap); shutdown() must release it or an elastic re-init raises
# "already initialized" (reference: the shutdown/init reset cycle, §3.5).
_jax_distributed_active = False


def _state() -> _GlobalState:
    if _global_state is None:
        raise NotInitializedError()
    return _global_state


def is_initialized() -> bool:
    return _global_state is not None


def init(
    process_sets: Optional[Sequence[Sequence[int]]] = None,
    *,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> None:
    """Initialize the runtime (reference: operations.cc `horovod_init`).

    Single-process: builds the global mesh over all visible devices.
    Multi-process (multi-host pod): pass coordinator_address/num_processes/
    process_id, or set HOROVOD_COORDINATOR_ADDR / HOROVOD_NUM_PROCESSES /
    HOROVOD_PROCESS_ID (injected by `horovodrun_tpu`), and the runtime calls
    `jax.distributed.initialize` — the gRPC-over-DCN bootstrap that replaces
    Horovod's MPI/Gloo rendezvous.

    `process_sets`: list of rank lists to pre-register (reference:
    horovod_init's process-set argument).
    """
    global _global_state
    with _init_lock:
        if _global_state is not None:
            logger.debug("horovod_tpu.init() called twice; ignoring")
            return

        util.configure_compile_cache()

        coordinator_address = coordinator_address or util.getenv("COORDINATOR_ADDR")
        if coordinator_address:
            num_processes = num_processes or util.env_int("NUM_PROCESSES", 1)
            process_id = (
                process_id
                if process_id is not None
                else util.env_int("PROCESS_ID", 0)
            )
            # Cross-process computations on the CPU backend need an
            # explicit collectives implementation (the default is none,
            # which raises "Multiprocess computations aren't implemented
            # on the CPU backend").  Must land before the first backend
            # client is created; harmless on TPU.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            global _jax_distributed_active
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            _jax_distributed_active = True

        devs = list(devices) if devices is not None else list(jax.devices())
        mesh = Mesh(np.asarray(devs), (GLOBAL_AXIS,))
        _global_state = _GlobalState(mesh, devs)

        if process_sets:
            for ranks in process_sets:
                add_process_set(ranks)

        # Aux subsystems, env-gated like the reference (SURVEY.md §5):
        # HOROVOD_TIMELINE / HOROVOD_STALL_CHECK_TIME_SECONDS.  Their
        # single source of truth is the module-level handle in each module
        # (timeline.get_timeline() / stall_inspector.get_inspector()).
        from ..utils import autotune as _at_mod
        from ..utils import stall_inspector as _stall_mod
        from ..utils import timeline as _tl_mod

        _tl_mod.init_from_env(rank())
        _stall_mod.init_from_env()
        _at_mod.init_from_env()

        # Metrics exposition (HOROVOD_METRICS_PORT) + the fallback KV
        # publisher for workers whose watchdog is disabled (the stall
        # inspector publishes snapshots itself when running).
        from ..metrics import exposition as _met_exp
        from ..metrics import fleet as _met_fleet
        from ..metrics import history as _met_hist

        _met_exp.init_from_env(_global_state.process_index,
                               _global_state.num_processes)
        _met_fleet.maybe_start_kv_publisher()
        _met_hist.init_from_env()

        logger.info(
            "horovod_tpu initialized: size=%d local_size=%d process=%d/%d "
            "platform=%s",
            _global_state.size,
            _global_state.local_size,
            _global_state.process_index,
            _global_state.num_processes,
            devs[0].platform if devs else "none",
        )


def shutdown() -> None:
    """Tear down runtime state (reference: operations.cc `horovod_shutdown`).

    Under SPMD there is no background thread to join; we drop the mesh and
    clear collective caches so a subsequent `init()` (elastic re-init) sees
    fresh topology.
    """
    global _global_state, _jax_distributed_active
    with _init_lock:
        if _global_state is None:
            return
        # Clear cached compiled collectives — they bake in the old mesh.
        from ..ops import collectives as _coll  # local import: avoid cycle
        from ..utils import autotune as _at_mod
        from ..utils import stall_inspector as _stall_mod
        from ..utils import timeline as _tl_mod

        from ..metrics import exposition as _met_exp
        from ..metrics import fleet as _met_fleet
        from ..metrics import history as _met_hist

        _coll.clear_caches()
        _tl_mod.stop_timeline()
        _stall_mod.shutdown_inspector()
        _at_mod.shutdown_manager()
        _met_fleet.stop_kv_publisher()
        _met_hist.stop_history()
        _met_exp.stop_server()
        _global_state = None
        # Elastic multi-process mode must also drop the live backends:
        # jax.distributed.initialize refuses to run once backends exist,
        # and the NEXT generation may need a distributed bootstrap even if
        # this one was single-process (scale-up from np=1).
        multiproc_elastic = (
            os.environ.get("HOROVOD_ELASTIC") == "1"
            and os.environ.get("HVD_TPU_MULTIPROCESS_JAX") == "1")
        if _jax_distributed_active:
            # Release the distributed client so the next init() (elastic
            # reset with a new coordinator/world size) can bootstrap a
            # fresh distributed runtime (verified: 2-process teardown →
            # re-init on a new coordinator works).
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001 — teardown best effort
                logger.warning("jax.distributed.shutdown failed: %s", e)
        if _jax_distributed_active or multiproc_elastic:
            try:
                import jax.extend as _jex
                _jex.backend.clear_backends()
            except Exception as e:  # noqa: BLE001
                logger.warning("clear_backends failed: %s", e)
        _jax_distributed_active = False


atexit.register(shutdown)


# ---------------------------------------------------------------------------
# Rank / size queries (reference: operations.cc horovod_rank/size/...)
# ---------------------------------------------------------------------------

def size() -> int:
    """Total number of ranks (= chips across the whole job)."""
    return _state().size


def rank() -> int:
    """Global rank of this process's first device.

    Preserves the Horovod idiom ``if hvd.rank() == 0``: JAX device order
    places process 0's devices first, so exactly one process sees rank 0.
    """
    st = _state()
    return st.local_device_ranks[0] if st.local_device_ranks else -1


def local_size() -> int:
    """Number of ranks (chips) driven by this controller process."""
    return _state().local_size


def local_rank() -> int:
    """Index of this process among processes on the same host.

    With the canonical one-process-per-host TPU deployment this is 0; under
    multi-process-per-host launches it is derived from the launcher env
    (HOROVOD_LOCAL_RANK) when present.
    """
    return util.env_int("LOCAL_RANK", 0)


def cross_size() -> int:
    """Number of controller processes (hosts) — reference cross_size."""
    return _state().num_processes


def cross_rank() -> int:
    """Index of this controller process — reference cross_rank."""
    return _state().process_index


def process_index() -> int:
    return _state().process_index


def num_processes() -> int:
    return _state().num_processes


def local_device_ranks() -> List[int]:
    """Global ranks of the devices this process drives (TPU-specific)."""
    return list(_state().local_device_ranks)


def is_homogeneous() -> bool:
    """True when every process drives the same number of chips."""
    st = _state()
    return st.size == st.local_size * st.num_processes


def global_mesh() -> Mesh:
    """The framework-wide 1-D device mesh (axis name `hvd`)."""
    return _state().mesh


def global_devices() -> List[jax.Device]:
    return list(_state().devices)


# ---------------------------------------------------------------------------
# Build-info queries (reference: basics.py nccl_built/mpi_built/... ;
# horovodrun --check-build)
# ---------------------------------------------------------------------------

def tpu_built() -> bool:
    """True when a TPU is attached.  Before `init()` this initializes the
    backend, and with it takes the chip for this process — so a launcher
    parent may ask (`--check-build`) or launch, never both."""
    devs = (_global_state.devices if _global_state is not None
            else jax.devices())
    return any(d.platform == "tpu" for d in devs)


def xla_built() -> bool:
    return True


def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_built() -> bool:
    # The pure-CPU path exists via JAX's CPU backend.
    return True


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    """Reference: basics.py cuda_built — constitutionally False here
    (the build target is TPU/XLA; BASELINE.json's no-CUDA constraint)."""
    return False


def rocm_built() -> bool:
    return False


def ddl_built() -> bool:
    """IBM DDL was removed upstream ~v0.21; kept for probe parity."""
    return False


def mpi_enabled() -> bool:
    """Reference: basics.py mpi_enabled — 'built' is compile-time,
    'enabled' is runtime availability.  No MPI in this runtime."""
    return False


def gloo_enabled() -> bool:
    """The gloo role (MPI-free rendezvous + CPU collectives) is always
    available: KV rendezvous + the JAX CPU backend."""
    return True


def mpi_threads_supported() -> bool:
    return False


# ---------------------------------------------------------------------------
# Process sets (reference: horovod/common/process_sets.py)
# ---------------------------------------------------------------------------

def add_process_set(ranks: Sequence[int]) -> ProcessSet:
    """Register a process set over `ranks` and build its sub-mesh."""
    st = _state()
    ranks = sorted(int(r) for r in ranks)
    if len(set(ranks)) != len(ranks):
        dups = sorted({r for r in ranks if ranks.count(r) > 1})
        raise HorovodTpuError(
            f"process set ranks contain duplicates {dups}: each rank "
            "may appear at most once (a duplicated rank would reach XLA "
            "as a non-partition axis_index_groups and fail opaquely)")
    if any(r < 0 or r >= st.size for r in ranks):
        raise HorovodTpuError(f"process set ranks {ranks} out of range")
    sub_devices = np.asarray([st.devices[r] for r in ranks])
    ps = ProcessSet(ranks=ranks, mesh=Mesh(sub_devices, (GLOBAL_AXIS,)))
    st.process_set_table.add(ps)
    return ps


def remove_process_set(ps: ProcessSet) -> None:
    _state().process_set_table.remove(ps.process_set_id)
    from ..ops import collectives as _coll

    _coll.clear_caches()


def get_process_set(ps_id: int) -> ProcessSet:
    return _state().process_set_table.get(ps_id)


def global_process_set() -> ProcessSet:
    return _state().process_set_table.get(0)
