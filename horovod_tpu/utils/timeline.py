"""Chrome-trace timeline profiler.

Reference parity (SURVEY.md §2.1, §5):
  - horovod/common/timeline.cc/.h `Timeline` / `TimelineWriter` /
    `TimelineController` → `Timeline` / `_TimelineWriter` here
  - env `HOROVOD_TIMELINE=/path.json` enables it at `hvd.init()`;
    `HOROVOD_TIMELINE_MARK_CYCLES=1` marks step cycles
  - per-tensor phases NEGOTIATE→QUEUE→MEMCPY_IN_FUSION_BUFFER→
    NCCL_ALLREDUCE→MEMCPY_OUT_FUSION_BUFFER become the TPU-native phases
    ENQUEUE (host staging) → COMPILE (first-call trace+compile, the moral
    analog of negotiation: it happens once per shape, not per step) →
    EXECUTE (XLA program incl. the ICI collective)

TPU-native redesign: the reference writes events from the background
coordination thread as each tensor moves through negotiation and the fusion
buffer.  Under SPMD those stages happen inside one compiled program, so the
device-side story belongs to `jax.profiler` (perfetto); this timeline covers
the *host-side control plane* — eager collective dispatch, compile hits, step
cycles, elastic events — in the same Chrome ``chrome://tracing`` JSON format
the reference emits, so the two traces can be viewed with the same tooling.

`span(...)` at the bottom is the one place the program writes a host
span onto the profiler's own clock (and, with a timeline active, into
this file as well): docs/TIMELINE.md.

The writer mirrors the reference design: events are appended to an in-memory
queue by the hot path (no IO), and a dedicated writer thread drains it to
disk (`TimelineWriter` with its short-circuit buffer, timeline.cc).
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import queue
import threading
import time
from typing import Optional

from ..common import util

logger = logging.getLogger("horovod_tpu.timeline")


class _TimelineWriter:
    """Background thread draining event records to a Chrome-trace JSON file.

    Reference: timeline.cc `TimelineWriter` — own thread, lock-free-ish
    handoff.  We use a `queue.Queue`; the hot path only does `put_nowait`.
    """

    _SENTINEL = object()

    def __init__(self, filename: str):
        self.filename = filename
        self._queue: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name="hvd-timeline-writer", daemon=True
        )
        self._healthy = True
        self._thread.start()

    def enqueue(self, record: dict) -> None:
        if self._healthy:
            self._queue.put_nowait(record)

    def _run(self) -> None:
        try:
            with open(self.filename, "w") as f:
                # Chrome trace "JSON Array Format": open bracket, one event
                # per line; readers accept a missing close bracket, so the
                # file is valid even if the process dies mid-run (same
                # property the reference relies on).
                f.write("[\n")
                first = True
                while True:
                    rec = self._queue.get()
                    if rec is _TimelineWriter._SENTINEL:
                        break
                    if not first:
                        f.write(",\n")
                    # default=str: event args may carry numpy/jax scalars.
                    f.write(json.dumps(rec, default=str))
                    first = False
                    # Flush only when the queue drains: under a burst of
                    # events a flush per record turns the writer thread
                    # into one syscall per event (the reference's writer
                    # batches for the same reason); an empty queue means
                    # nobody is waiting, so make the file current then.
                    if self._queue.empty():
                        f.flush()
                f.write("\n]\n")
        except Exception:
            # Mark unhealthy so the hot path stops feeding a dead writer
            # (otherwise the queue grows unboundedly).
            self._healthy = False

    def close(self) -> None:
        if self._thread.is_alive():
            self._queue.put(_TimelineWriter._SENTINEL)
            self._thread.join(timeout=5)


class _NativeWriterAdapter:
    """Routes records into the C++ buffered writer thread
    (horovod_tpu/_native: TimelineWriter, reference timeline.cc)."""

    def __init__(self, filename: str):
        from .._native import load
        from .._native.control_plane import NativeTimelineWriter
        # Only accept a prebuilt library here: this runs inside
        # hvd.init() and must not trigger a synchronous g++ build.
        if load(build_if_missing=False) is None:
            raise RuntimeError("native library not prebuilt")
        self.filename = filename
        self._w = NativeTimelineWriter(filename)

    # Chrome-trace keys the native writer's fixed parameter list covers.
    _KNOWN = frozenset(("name", "cat", "ph", "ts", "dur", "pid", "tid",
                        "s", "args"))

    def enqueue(self, record: dict) -> None:
        args = record.get("args")
        # Keys outside the fixed set ("id" pairing async/flow events,
        # "bp", ...) must survive the round trip top-level — folding
        # them into args (or dropping them, the old behavior) breaks
        # chrome://tracing's event pairing.
        extra = {k: v for k, v in record.items() if k not in self._KNOWN}
        self._w.event(
            name=str(record.get("name", "")),
            cat=str(record.get("cat", "")),
            ph=str(record.get("ph", "i")),
            ts_us=float(record.get("ts", 0.0)),
            dur_us=float(record.get("dur", -1.0)),
            pid=int(record.get("pid", 0)),
            tid=str(record.get("tid", "")),
            scope=str(record.get("s", "")),
            args_json=json.dumps(args, default=str) if args else "",
            extra_json=(json.dumps(extra, default=str)[1:-1]
                        if extra else ""),
        )

    def close(self) -> None:
        self._w.close()


def _make_writer(filename: str):
    """Prefer the native C++ writer; fall back to the Python thread."""
    if not util.env_bool("TIMELINE_DISABLE_NATIVE", False):
        try:
            return _NativeWriterAdapter(filename)
        except Exception as e:  # noqa: BLE001 — native engine optional
            logger.debug("native timeline writer unavailable (%s); "
                         "using the Python writer", e)
    return _TimelineWriter(filename)


class Timeline:
    """Per-process timeline of control-plane activities.

    Chrome-trace mapping: pid = global rank, tid = tensor/activity name.
    Complete events (`ph="X"`) are emitted on activity end so each phase is
    a single record (the reference emits B/E pairs; X halves the volume).
    """

    def __init__(self, filename: str, rank: int = 0,
                 mark_cycles: bool = False):
        self._writer = _make_writer(filename)
        self._rank = rank
        self._mark_cycles = mark_cycles
        # token -> (tensor_name, activity, start_us); tokens are unique per
        # bracket so concurrent unnamed collectives never collide.
        self._starts: dict = {}
        self._next_token = 0
        self._lock = threading.Lock()
        self._cycle = 0
        self._t0 = time.perf_counter()

    # -- clock ------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def now_us(self, at: Optional[float] = None) -> float:
        """Timeline-clock timestamp, for `complete()` callers bracketing
        their own spans: now, or of the `time.perf_counter()` reading
        `at` (the clock is that one with an origin, so a caller that
        already holds a reading hands it over and reads no second one)."""
        return self._now_us() if at is None else (at - self._t0) * 1e6

    @property
    def current_cycle(self) -> int:
        """Cycles marked so far (= completed steps when the pipeline marks
        one cycle per step)."""
        return self._cycle

    def _step_stamp(self) -> dict:
        # Stable step ID for the cross-rank merger (horovod_tpu/trace):
        # the number of completed cycles when the event fired.  Emitted as
        # a TOP-LEVEL key — chrome://tracing ignores unknown keys and the
        # native writer round-trips them via extra_json — so event `args`
        # stay exactly what the call site passed.
        return {"step": self._cycle} if self._mark_cycles else {}

    # -- per-tensor activities (reference: ActivityStart/ActivityEnd) -----
    def activity_start(self, tensor_name: str, activity: str) -> int:
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._starts[token] = (tensor_name, activity, self._now_us(),
                                   self._cycle)
        return token

    def activity_end(self, token: int) -> None:
        now = self._now_us()
        with self._lock:
            entry = self._starts.pop(token, None)
        if entry is None:
            return
        tensor_name, activity, start, cycle = entry
        self._writer.enqueue({
            "name": activity,
            "cat": "collective",
            "ph": "X",
            "ts": round(start, 1),
            "dur": round(now - start, 1),
            "pid": self._rank,
            "tid": tensor_name,
            # Stamp the step the collective STARTED in, so a bracket that
            # straddles a cycle mark stays attributed to its issue step.
            **({"step": cycle} if self._mark_cycles else {}),
        })

    # -- instant events ---------------------------------------------------
    def instant(self, name: str, category: str = "event",
                args: Optional[dict] = None,
                tid: Optional[str] = None) -> None:
        self._writer.enqueue({
            "name": name,
            "cat": category,
            "ph": "i",
            "s": "p",
            "ts": round(self._now_us(), 1),
            "pid": self._rank,
            "tid": tid if tid is not None else category,
            **self._step_stamp(),
            **({"args": args} if args else {}),
        })

    # -- complete spans with caller-held start (trace span model) ---------
    def complete(self, name: str, category: str, start_us: float,
                 args: Optional[dict] = None,
                 tid: Optional[str] = None) -> None:
        """Emit a `ph="X"` span from a caller-captured `now_us()` start to
        now — the per-step host span the fleet tracer's critical-path
        analysis consumes.  `tid` defaults to the category (the training
        step lane); the serve layer overrides it with `req/<id>` so every
        request renders as its own Gantt row (docs/TIMELINE.md)."""
        now = self._now_us()
        self._writer.enqueue({
            "name": name,
            "cat": category,
            "ph": "X",
            "ts": round(start_us, 1),
            "dur": round(now - start_us, 1),
            "pid": self._rank,
            "tid": tid if tid is not None else category,
            **self._step_stamp(),
            **({"args": args} if args else {}),
        })

    # -- cycle marks (reference: HOROVOD_TIMELINE_MARK_CYCLES) ------------
    def mark_cycle(self) -> None:
        if not self._mark_cycles:
            return
        self._cycle += 1
        self.instant(f"CYCLE_{self._cycle}", category="cycle")

    def close(self) -> None:
        self._writer.close()


# ---------------------------------------------------------------------------
# Module-level hooks used by the collectives hot path.  Kept as a plain
# global so the disabled-case check is one attribute load (the reference
# guards every Timeline call on `timeline_enabled_`).
# ---------------------------------------------------------------------------

_timeline: Optional[Timeline] = None


def get_timeline() -> Optional[Timeline]:
    return _timeline


def start_timeline(filename: str, rank: int = 0,
                   mark_cycles: Optional[bool] = None) -> Timeline:
    """Programmatic start (reference: horovod_start_timeline API)."""
    global _timeline
    stop_timeline()
    if mark_cycles is None:
        mark_cycles = util.env_bool("TIMELINE_MARK_CYCLES", False)
    _timeline = Timeline(filename, rank=rank, mark_cycles=mark_cycles)
    return _timeline


def stop_timeline() -> None:
    global _timeline
    if _timeline is not None:
        _timeline.close()
        _timeline = None


_TraceAnnotation = None


class span:
    """One host span of the program, `with span(name, category, args)`.

    It is always a `jax.profiler.TraceAnnotation` named
    ``hvd.<category>.<name>`` carrying `args`: whenever a profiler
    session is open (`utils/profiler.start_device_trace`, a benchmark's
    traced run, an operator's own `jax.profiler.trace`) the span lies in
    the same file and on the same clock as the device's operations, so
    an idle gap of the device can be put down to it with no merge step.
    With a `Timeline` active it is also that timeline's `complete(...)`
    event (same name, category, args, `tid`), written on leaving.  With
    neither on it reads no clock and costs about a microsecond (1.1 us
    bare, 1.4 us with five arguments, on the host of a v5e: PERF.md 6,
    PR 40).  An annotation takes its arguments when it opens, so
    `args` holds what is known then.  Its parent is the enclosing span
    on the same thread.
    """

    __slots__ = ("_name", "_category", "_args", "_tid", "_ann", "_tl",
                 "_start_us")

    def __init__(self, name: str, category: str,
                 args: Optional[dict] = None, tid: Optional[str] = None):
        self._name, self._category = name, category
        self._args, self._tid = args, tid

    def __enter__(self) -> "span":
        global _TraceAnnotation
        if _TraceAnnotation is None:        # jax is imported lazily here
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self._ann = _TraceAnnotation(
            f"hvd.{self._category}.{self._name}", **(self._args or {}))
        self._ann.__enter__()
        self._tl = _timeline
        if self._tl is not None:
            self._start_us = self._tl.now_us()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        # Written only to the timeline that saw the span open: one
        # started meanwhile has no start stamp on its clock.
        if self._tl is not None and self._tl is _timeline:
            self._tl.complete(self._name, self._category, self._start_us,
                              args=self._args, tid=self._tid)


# Close the trace (emitting the closing bracket / draining the native
# buffer) even when users never call hvd.shutdown(); stop_timeline() is
# idempotent, so the normal shutdown path stays unaffected.
atexit.register(stop_timeline)


def init_from_env(rank: int) -> None:
    """Called by `hvd.init()`: honor HOROVOD_TIMELINE like the reference.

    Like the reference, only rank 0 writes (timeline.cc gates on rank)
    unless HOROVOD_TIMELINE_ALL_RANKS is set, in which case the filename
    gets a per-rank suffix.
    """
    fname = util.getenv("TIMELINE")
    if not fname:
        return
    all_ranks = util.env_bool("TIMELINE_ALL_RANKS", False)
    if rank != 0 and not all_ranks:
        return
    if all_ranks and rank != 0:
        base, ext = os.path.splitext(fname)
        fname = f"{base}.rank{rank}{ext or '.json'}"
    start_timeline(fname, rank=rank)
