"""Static launch path: spawn one worker per slot with derived env.

Reference parity: horovod/runner/gloo_run.py (`gloo_run`) — compute host
assignments, start the rendezvous server, exec each slot's command (local
fork or SSH), stream prefixed output, tear the tree down on failure.

TPU-native differences: workers bootstrap through
`jax.distributed.initialize` (coordinator = rank-0 host), so the env
contract is HOROVOD_COORDINATOR_ADDR/NUM_PROCESSES/PROCESS_ID plus the
classic HOROVOD_RANK/SIZE/LOCAL_RANK/... set, and the rendezvous KV serves
the control plane only.  A chip belongs to one process at a time, so
several slots on one TPU host each get a chip of their own through
libtpu's per-process variables (`tpu_chip_env`).
"""

from __future__ import annotations

import logging
import os
import shlex
import socket
import time
from typing import Dict, List, Optional

from ..common.exceptions import HorovodTpuError
from . import safe_exec
from .safe_exec import GRACEFUL_TERMINATION_TIME_S
from .hosts import SlotInfo
from .rendezvous import RendezvousServer
from .settings import Settings

logger = logging.getLogger("horovod_tpu.runner")

LOCAL_HOSTNAMES = ("localhost", "127.0.0.1", socket.gethostname())

# Port the rank-0 worker binds its jax.distributed coordinator to when it
# runs on a remote host (free-port probing is only possible locally).
DEFAULT_COORDINATOR_PORT = 46327


# libtpu's arrangement of one-chip processes over the chips of ONE host,
# by slots on it (the table jax's own multi-process TPU test launcher
# uses).  4 = the v5e 2x2 host this was run on; 8 is taken from that
# table and has not been run here.
_TPU_PROCESS_BOUNDS = {4: "2,2,1", 8: "4,2,1"}
# libtpu's default port for the first process of a host; slot i takes +i.
_TPU_PROCESS_PORT = 8476


def tpu_chip_env(slot: SlotInfo, env: Dict[str, str]) -> Dict[str, str]:
    """Variables that make local slot i own chip i and nothing else, so
    that N slots on a host are N one-chip ranks of one job (Horovod's
    one rank per accelerator) instead of N processes reaching for every
    chip.  Nothing is needed for one slot a host — that process drives
    all the local chips — or when `env` sends the job to the CPU.  A
    layout that cannot be given a chip a slot is refused here, before
    anything is spawned."""
    if slot.local_size == 1 or \
            env.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return {}
    bounds = _TPU_PROCESS_BOUNDS.get(slot.local_size)
    if bounds is None or slot.cross_size != 1:
        raise HorovodTpuError(
            f"cannot give each of {slot.local_size} slots on "
            f"{slot.hostname} a TPU chip of its own: one chip a process "
            f"is supported on a single host with "
            f"{sorted(_TPU_PROCESS_BOUNDS)} slots.  Use one slot per "
            f"host (-H {slot.hostname}:1 — that process drives all the "
            f"host's chips), or set JAX_PLATFORMS=cpu if the job does "
            f"not run on TPUs.")
    ports = [_TPU_PROCESS_PORT + i for i in range(slot.local_size)]
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[slot.local_rank]),
        "TPU_VISIBLE_CHIPS": str(slot.local_rank),
        "CLOUD_TPU_TASK_ID": str(slot.local_rank),
        # libtpu otherwise refuses a second process on the host
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def _is_local(hostname: str) -> bool:
    if hostname in LOCAL_HOSTNAMES:
        return True
    # Test hook (reference uses the same localhost fake-cluster pattern,
    # SURVEY.md §4): hostnames listed here exec locally instead of via ssh,
    # letting elastic integration tests blacklist "hosts" on one machine.
    fake = os.environ.get("HVD_TPU_FAKE_LOCAL_HOSTS")
    return bool(fake) and hostname in fake.split(",")


def slot_env(
    slot: SlotInfo,
    settings: Settings,
    secret: str,
    coordinator_addr: str,
) -> Dict[str, str]:
    """Derive the worker env for one slot (reference:
    runner/common/util/env.py + gloo_run's slot env injection)."""
    env = dict(os.environ)
    if settings.extra_env:
        env.update({k: str(v) for k, v in settings.extra_env.items()})
    env.update({
        "HOROVOD_RANK": str(slot.rank),
        "HOROVOD_SIZE": str(slot.size),
        "HOROVOD_LOCAL_RANK": str(slot.local_rank),
        "HOROVOD_LOCAL_SIZE": str(slot.local_size),
        "HOROVOD_CROSS_RANK": str(slot.cross_rank),
        "HOROVOD_CROSS_SIZE": str(slot.cross_size),
        "HOROVOD_CONTROLLER": "xla",
        "HOROVOD_CPU_OPERATIONS": "xla",
        # jax.distributed bootstrap (consumed by horovod_tpu.init()).
        "HOROVOD_COORDINATOR_ADDR": coordinator_addr,
        "HOROVOD_NUM_PROCESSES": str(slot.size),
        "HOROVOD_PROCESS_ID": str(slot.rank),
        # Control-plane rendezvous.
        "HOROVOD_RENDEZVOUS_ADDR": settings.rendezvous_addr or "127.0.0.1",
        "HOROVOD_RENDEZVOUS_PORT": str(settings.rendezvous_port or 0),
        "HOROVOD_SECRET_KEY": secret,
    })
    env.update(tpu_chip_env(slot, env))
    if settings.timeline_filename:
        # Workers handle per-rank suffixing themselves (timeline.py
        # init_from_env): rank 0 writes the base file; other ranks only
        # write when HOROVOD_TIMELINE_ALL_RANKS is set in the environment.
        env["HOROVOD_TIMELINE"] = settings.timeline_filename
        if settings.timeline_mark_cycles:
            env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if settings.fusion_threshold_mb is not None:
        env["HOROVOD_FUSION_THRESHOLD"] = str(
            settings.fusion_threshold_mb * 1024 * 1024)
    if settings.cycle_time_ms is not None:
        env["HOROVOD_CYCLE_TIME"] = str(settings.cycle_time_ms)
    if settings.cache_capacity is not None:
        env["HOROVOD_CACHE_CAPACITY"] = str(settings.cache_capacity)
    if settings.autotune:
        env["HOROVOD_AUTOTUNE"] = "1"
        if settings.autotune_log_file:
            env["HOROVOD_AUTOTUNE_LOG"] = settings.autotune_log_file
    if settings.stall_check_time_seconds is not None:
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = str(
            settings.stall_check_time_seconds)
    if settings.stall_shutdown_time_seconds is not None:
        env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = str(
            settings.stall_shutdown_time_seconds)
    if settings.log_level:
        env["HOROVOD_LOG_LEVEL"] = settings.log_level
    return env


def build_command(slot: SlotInfo, settings: Settings,
                  env: Dict[str, str]) -> List[str]:
    """Local slots exec directly; remote slots go through ssh with the env
    serialized onto the remote command line (reference: gloo_run's
    get_remote_command)."""
    if not settings.command:
        raise HorovodTpuError("no command to launch: settings.command "
                              "is empty")
    if _is_local(slot.hostname):
        return list(settings.command)
    ssh = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if settings.ssh_port:
        ssh += ["-p", str(settings.ssh_port)]
    if settings.ssh_identity_file:
        ssh += ["-i", settings.ssh_identity_file]
    exported = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env.items()
        if k.startswith(("HOROVOD_", "HVD_TPU_", "JAX_", "XLA_", "TPU_",
                         "PYTHONPATH", "PATH")))
    remote_cmd = f"cd {shlex.quote(os.getcwd())} && env {exported} " + \
        " ".join(shlex.quote(c) for c in settings.command)
    return ssh + [slot.hostname, remote_cmd]


def exec_run(settings: Settings, slots: List[SlotInfo],
             result_hook=None) -> int:
    """Launch all slots, wait, propagate the first failure (reference:
    gloo_run → launch_gloo).

    `result_hook(server)`, if given, runs after all workers exit but
    before the rendezvous server stops — the `run()` API uses it to pull
    worker results out of the KV store."""
    server = RendezvousServer(verbose=settings.verbose)
    port = server.start()
    settings.rendezvous_addr = settings.rendezvous_addr or _my_addr(
        slots, settings.nics)
    settings.rendezvous_port = port

    # The jax.distributed coordinator is bound by the rank-0 *worker*, so
    # the address must be reachable from every other worker's host.  For a
    # remote rank-0 host we cannot probe a free port there; use a fixed
    # well-known port (overridable via --coordinator-port / Settings).
    all_local = all(_is_local(s.hostname) for s in slots)
    if _is_local(slots[0].hostname):
        coord_host = ("127.0.0.1" if all_local and not settings.nics
                      else _my_addr(slots, settings.nics))
        coord_port = settings.coordinator_port or _free_port()
    else:
        coord_host = slots[0].hostname
        coord_port = settings.coordinator_port or DEFAULT_COORDINATOR_PORT
    coordinator_addr = f"{coord_host}:{coord_port}"

    procs = []
    out_files = []
    try:
        # Every env first: a layout slot_env refuses must spawn nothing.
        envs = [slot_env(slot, settings, server.secret, coordinator_addr)
                for slot in slots]
        for slot, env in zip(slots, envs):
            cmd = build_command(slot, settings, env)
            stdout = stderr = None
            if settings.output_filename:
                os.makedirs(settings.output_filename, exist_ok=True)
                f = open(os.path.join(
                    settings.output_filename, f"rank.{slot.rank}.log"), "w")
                out_files.append(f)
                stdout = stderr = f
            procs.append(safe_exec.execute(
                cmd, env=env, prefix=str(slot.rank),
                stdout=stdout, stderr=stderr, background=True))
            logger.debug("launched rank %d on %s (pid %d)",
                         slot.rank, slot.hostname, procs[-1].pid)

        # Wait for all; on any nonzero exit, terminate the rest.
        exit_code = 0
        pending = {p.pid: (s, p) for s, p in zip(slots, procs)}
        while pending:
            for pid in list(pending):
                slot, proc = pending[pid]
                rc = proc.poll()
                if rc is None:
                    continue
                del pending[pid]
                if rc != 0:
                    logger.error("rank %d (pid %d) exited with code %d",
                                 slot.rank, pid, rc)
                    exit_code = exit_code or rc
                    for _, other in pending.values():
                        other.terminate()
            time.sleep(0.1)
        if result_hook is not None and exit_code == 0:
            result_hook(server)
        return exit_code
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        # Drain the output-forwarder threads before closing the log files,
        # or the tail of a failing rank's traceback is lost.
        for p in procs:
            try:
                p.wait(timeout=GRACEFUL_TERMINATION_TIME_S)
            except Exception as e:  # noqa: BLE001 — kill follows anyway
                logger.debug("pid %d did not exit in %ss (%s)",
                             p.pid, GRACEFUL_TERMINATION_TIME_S, e)
        for f in out_files:
            f.close()
        server.stop()


def _my_addr(slots: List[SlotInfo], nics: Optional[str] = None) -> str:
    """Address workers use to reach the launcher's rendezvous server.

    `nics` (--network-interfaces) pins the advertised interface; see
    runner/network.py (reference: driver_service NIC selection).
    """
    from . import network

    if nics:
        return network.resolve_advertise_address(nics)
    if all(_is_local(s.hostname) for s in slots):
        return "127.0.0.1"
    # Multi-host: pick the interface routing toward the first remote host.
    remote = next(s.hostname for s in slots if not _is_local(s.hostname))
    return network.resolve_advertise_address(None, remote)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]
