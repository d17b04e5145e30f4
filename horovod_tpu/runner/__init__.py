"""Launcher / CLI / cluster bootstrap (reference: horovod/runner/).

Reference parity map (SURVEY.md §2.5, §3.1):
  - horovod/runner/launch.py (`horovodrun`, `parse_args`, `run_commandline`)
      → `launch.py` (`horovodrun_tpu`, `python -m horovod_tpu.runner`)
  - horovod/runner/__init__.py `run()`         → `run()` below
  - horovod/runner/common/util/hosts.py        → `hosts.py`
  - horovod/runner/common/util/settings.py     → `settings.py`
  - horovod/runner/common/util/safe_shell_exec.py → `safe_exec.py`
  - horovod/runner/http/http_server.py (RendezvousServer KV)
      → `rendezvous.py` (TCP KV store, C++ backend when built)
  - horovod/runner/gloo_run.py                 → `exec_run.py`

TPU-native redesign: there is no MPI path, and `jax.distributed` (gRPC
over DCN) replaces the MPI/Gloo controller bootstrap.  A chip belongs to
one process at a time, so a job is laid out in one of two ways: one slot
per host (`-H a:1,b:1`), whose process drives all of that host's chips;
or, on a single host, one slot per chip (`-np 4 -H localhost:4`), each
handed its own chip by `exec_run.tpu_chip_env` — Horovod's one rank per
accelerator.  Several slots per host across several hosts are refused.
The launcher itself never touches the backend.  The KV rendezvous store
remains for what XLA does not give us: elastic membership, barriers,
health, and stall reporting.
"""

from .api import run  # noqa: F401
from .hosts import (  # noqa: F401
    HostInfo,
    SlotInfo,
    parse_hosts,
    parse_hostfile,
    get_host_assignments,
)
from .settings import Settings  # noqa: F401
