"""Continuous-batching inference server over a model's decode cache.

One compiled decode step of fixed ``max_batch`` rows serves every
in-flight sequence; admission/eviction happens BETWEEN steps (the
scheduler), and sequence state lives in the cache (pool.py: pages, or
a state a row), which the server knows as a ``DecodeCache`` and no
closer, the draft's like the target's.  The decode kernels run UNCHANGED — the only model-side addition is the
vector-``pos`` path in ``models/decode.py``, because continuously
batched rows sit at different depths of the same step.

Step anatomy (``step()``; in brackets what a paged cache does).  ONE
decode step is kept in flight: iteration i dispatches step i BEFORE it
waits for step i-1's ids, so the device has step i queued while the
host does the rest of its work:

  1. admit   — queued requests ``board`` free rows: prefill-on-admit
               runs ``transformer_prefill`` into a scratch cache [of the
               prompt's pages, bulk-written into the first of the
               request's pages; the rest of its budget is zeroed].
  2. sample  — a row whose pending id the HOST holds (a row just
               admitted: the argmax of its prefill's row of logits;
               after ``last_logits`` was assigned; after a speculative
               round) emits it and is fed it; a row whose pending id is
               the step in flight's is fed ``-1``, "the device's": the
               decode program picks its token from the ids the step
               before left on the device.  Finished rows evict and
               ``release`` their row BEFORE any device work; a row whose
               pending id is its ``max_new_tokens``-th is left out of
               the step, so the last token costs no decode step.
  3. launch  — [``put``: refresh the pooled view if membership changed,]
               put the step's two ``[max_batch]`` integer arrays on the
               device, dispatch one vector-pos
               ``transformer_decode_step`` with the greedy pick in it
               (``_serve_step_fn``), which CONSUMES the view (donated,
               updated in place): the cache lends it and takes back the
               result.  ``ahead`` says whether the step before was still
               in flight (``steps_ahead`` counts those).
               [``write_through``: copy each active row's written ring
               slot(s) into its pages; the pool stays the source of
               truth.]
  4. fetch   — the iteration's one sync: the ``[max_batch]`` ids of the
               step BEFORE the one just dispatched.  They are emitted
               here, rows they end are evicted, and a row they end by
               EOS is dropped from the step just dispatched, which
               stepped it once too often: that id is thrown away
               (``rows_dropped``), nothing of it is left behind.  The
               ``[max_batch, vocab]`` logits stay on the device and
               come to the host only for who reads ``last_logits``.
  5. observe — counts, latency, gauges, the flight recorder.

Whatever is not a plain step after a plain step first LANDS the step in
flight (waits for its ids, which become pending ids the host holds) and
then runs as it would with none: a speculative round (draft chain +
chunked verify, when the SLO controller has flipped speculation on),
``last_logits`` assigned, a crash's flight record.  An iteration that
finds no row to step only fetches.

Speculative rounds keep the greedy target chain EXACT: every decided
token is the argmax of target logits computed over a correct prefix
(accepted-prefix min over rows; stale speculative slots are never
readable before they are overwritten — the same always-write-before-
read ring property ``transformer_speculative_generate`` relies on).

The host's side of a step is five spans under one
(`utils/timeline.span`, category ``serve``: ``step`` holding ``admit``
with one ``prefill`` a request, ``sample``, ``launch`` with ``put`` and
``write_through`` inside, ``fetch``, ``observe``; docs/SERVING.md has
the table), so a profiler trace shows which of them the device waits
for; ``launch`` says what work the step it dispatches is, ``observe``
what the step whose ids were fetched did (both carry ``dstep``).

All host orchestration (clocks, metrics, env) stays OUTSIDE the jitted
programs; the compiled pieces are the same module-cached
``_spec_step_fn`` / ``_spec_extend_fn`` programs the speculative
decoder uses, ``_serve_step_fn`` (the step with the greedy pick in it)
and one prefill jit — shapes (max_batch, view ring,
gamma) key the program cache through tracing, which is why the
``serve_page_tokens`` / ``serve_max_batch`` / ``serve_spec_gamma``
autotuner knobs are part of the compiled-shape key (docs/AUTOTUNE.md).
"""

from __future__ import annotations

import atexit
import contextlib
import functools
import time
import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common import util
from ..common.exceptions import InvalidRequestError
from ..metrics import catalog as _met
from ..models.decode import (_serve_step_fn, _spec_extend_fn,
                             _spec_step_fn, serve_ids_len,
                             transformer_prefill)
from ..models.experts import ROUTED
from ..utils import autotune
from ..utils.timeline import get_timeline, span
from .flightrec import FlightRecorder
from .pool import PoolExhaustedError, make_cache
from .scheduler import ActiveSeq, ContinuousScheduler, Request
from .slo import SloController


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg):
    # Consumes the scratch cache like the step programs consume the
    # view (models/decode.py): a cache's `board` makes it and rebinds it.
    return jax.jit(lambda p, c, t: transformer_prefill(p, c, t, cfg),
                   donate_argnums=(1,))


def _flush_at_exit(ref: "weakref.ref") -> None:
    srv = ref()
    if srv is not None:
        srv.flush_metrics()


class InferenceServer:
    """Greedy continuous-batching decode server (one model replica).

    ``policy="static"`` turns the SAME machinery into the static-
    batching baseline (admit only into an empty batch) — the bench's
    A/B isolates the batching policy exactly.
    """

    def __init__(self, params, cfg, *,
                 max_seq_tokens: int,
                 max_batch: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 quantize: Optional[str] = None,
                 draft_params=None, draft_cfg=None,
                 gamma: Optional[int] = None,
                 slo_ms: Optional[float] = None,
                 force_spec: bool = False,
                 policy: str = "fifo", seed: int = 0):
        self.params = params
        self.cfg = cfg
        self.page_tokens = page_tokens or \
            autotune.current_serve_page_tokens()
        self.max_batch = max_batch or autotune.current_serve_max_batch()
        self.gamma = gamma or autotune.current_serve_spec_gamma()
        if self.page_tokens < 1 or self.max_batch < 1 or self.gamma < 1:
            raise InvalidRequestError(
                f"page_tokens/max_batch/gamma must be >= 1, got "
                f"{self.page_tokens}/{self.max_batch}/{self.gamma}")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        if (draft_params is None) != (draft_cfg is None):
            raise InvalidRequestError(
                "draft_params and draft_cfg come together")
        if draft_params is not None and cfg.attn_window:
            raise InvalidRequestError(
                "speculative serving does not support attn_window "
                "configs (chunked verify over a rolling ring)")
        # Per-sequence budget: the full ring a request may need.  The
        # gamma headroom mirrors transformer_speculative_generate — a
        # round writes up to gamma slots past the accepted frontier.
        self._headroom = self.gamma if draft_params is not None else 0
        self.max_seq_tokens = max_seq_tokens + self._headroom
        self.view_pages = -(-self.max_seq_tokens // self.page_tokens)
        pool_pages = pool_pages or autotune.current_serve_pool_pages() \
            or self.max_batch * self.view_pages
        self.sched = ContinuousScheduler(self.max_batch, policy=policy,
                                         seed=seed)
        # The target's cache and the draft's (or None), and each with
        # the weights that fill it: what both need is one loop.
        shape = dict(rows=self.max_batch, view_pages=self.view_pages,
                     page_tokens=self.page_tokens, pool_pages=pool_pages,
                     speculative=draft_params is not None,
                     rows_held=lambda: len(self.sched.active))
        self.pool = make_cache(cfg, quantize=quantize, **shape)
        self._caches = [(self.pool, params)]
        self.dpool = None
        if draft_params is not None:
            self.dpool = make_cache(draft_cfg, quantize=None, **shape)
            self._caches.append((self.dpool, draft_params))
        if slo_ms is None:                 # HOROVOD_SERVE_SLO_MS
            slo_ms = util.env_float("SERVE_SLO_MS", 0.0)
        self.slo = SloController(slo_ms)
        self.force_spec = force_spec
        # Gauge sampling cadence (HOROVOD_SERVE_METRICS_INTERVAL): the
        # p99 percentile over the SLO window costs more than a whole
        # decode dispatch on small models, so gauges are sampled, with
        # one unconditional flush at drain/atexit (flush_metrics) so
        # runs shorter than the interval still report.
        self._metrics_interval = max(
            1, util.env_int("SERVE_METRICS_INTERVAL", 16))
        # Always-on flight recorder (docs/SERVING.md): depth <= 0
        # disables it.  Host-side only — the depth knob never touches
        # compiled shapes (host_only in autotune, out of the program-
        # cache key).
        depth = autotune.current_serve_flightrec_depth()
        self.flightrec: Optional[FlightRecorder] = \
            FlightRecorder(depth) if depth > 0 else None
        if self.flightrec is not None:
            rec = self.flightrec
            self.sched.observer = lambda step, event, req, row: \
                rec.record("sched", {"event": event, "req": req,
                                     "row": row}, step=step)
            for name, (cache, _) in zip(("pool", "dpool"), self._caches):
                cache.on_event = lambda ev, sid, n, free, name=name: \
                    rec.record(name, {"event": ev, "req": sid,
                                      "pages": n, "free": free},
                               step=self.step_no)
        self.slo.on_flip = self._on_slo_flip
        # Per-request lifecycle state feeding the timeline spans, the
        # latency histograms, and the flight recorder.
        self._req_obs: Dict[int, Dict] = {}
        # atexit flush through a weakref so short-lived servers (tests,
        # benches) are still collectable.
        ref = weakref.ref(self)
        atexit.register(_flush_at_exit, ref)

        V = cfg.vocab_size
        self.row_pos = np.zeros(self.max_batch, np.int64)
        # Each row's pending decision is an id.  The host holds it in
        # `_next_ids`, or it is the step in flight's and `_next_ids` says
        # -1, which is also what the row is fed: "the device's".  The
        # logits behind the ids are the last step's, left on the device,
        # under the rows decided on the host since (`_fresh`: a
        # prefill's row, a speculative round's); `last_logits` puts the
        # two together.
        self._next_ids = np.zeros(self.max_batch, np.int32)
        self._logits = np.zeros((self.max_batch, V), np.float32)
        self._fresh: Dict[int, np.ndarray] = {}
        self.logit_fetches = 0      # whole logits pulled to the host
        # The step dispatched whose ids have not reached the host: its
        # `dstep` and `rows` as its `launch` said them, its `ids` and
        # the rows it stepped whose ids count (`live`).  `_ids` are the last step's, in flight or landed:
        # the next step's `prev`.
        self._flight: Optional[Dict] = None
        self._ids = jax.device_put(
            np.zeros(serve_ids_len(cfg, self.max_batch), np.int32))
        # What this iteration's sync brought, and of which device step
        # (`_land`), for `observe`; empty where none was made.
        self._synced: Dict = {}
        self._ended: List[ActiveSeq] = []   # finished this iteration
        self.step_no = 0
        self._next_req_id = 0
        self._submit_wall: Dict[int, float] = {}
        # run stats (read by loadgen / the bench).  `device_steps` and
        # `occupancy_sum` count the steps whose ids have reached the
        # host (`_count_step`), as the routing sums below do.
        self.tokens_out = 0
        self.device_steps = 0
        self.spec_steps = 0
        # Plain steps dispatched while the step before was in flight,
        # and rows such a step stepped once too often (an EOS found a
        # step late), whose id was thrown away.
        self.steps_ahead = 0
        self.rows_dropped = 0
        self.occupancy_sum = 0.0
        # What a patterned model's expert layers counted (models/experts.py
        # `ROUTED`), summed over layers and steps: distinct experts a
        # token chose, the most tokens one expert took, the (token,
        # expert) pairs whose expert is held here, and how many (layer,
        # step) pairs the sums run over.
        self.experts_hit_sum = 0
        self.expert_load_max_sum = 0
        self.pairs_here_sum = 0
        self.moe_layer_steps = 0
        self.token_latencies_ms: List[float] = []
        self.request_latencies_ms: List[float] = []

    # What tests and the benchmark read of the caches (`retention`: the
    # cache is a state); the server's own code asks the caches.
    state_bytes = property(lambda self: self.pool.state_bytes)
    state_installs = property(lambda self: self.pool.installs)
    retention = property(lambda self: self.pool.state_bytes > 0)
    view_k, view_v, dview_k, dview_v = (
        property(lambda self, c=c, i=i: (
            getattr(getattr(self, c), "view", None) or (None, None))[i])
        for c in ("pool", "dpool") for i in (0, 1))

    @property
    def last_logits(self) -> np.ndarray:
        """The `[max_batch, vocab]` float32 logits the pending ids were
        picked from: the last step's (waited for, if it is in flight),
        with a row admitted since holding its prefill's.  Fetched from
        the device when asked (counted in `logit_fetches`) and kept
        until the next step; read-only, since a row written in place
        would move no id.  Assigning an array lands the step in flight
        and picks every pending id again from what was assigned."""
        if not isinstance(self._logits, np.ndarray):
            self._logits = np.array(self._logits)
            self.logit_fetches += 1
        for row, logits in self._fresh.items():
            self._logits[row] = logits
        self._fresh.clear()
        view = self._logits.view()
        view.flags.writeable = False
        return view

    @last_logits.setter
    def last_logits(self, logits) -> None:
        logits = np.array(logits, np.float32)
        if logits.shape != (self.max_batch, self.cfg.vocab_size):
            raise InvalidRequestError(
                f"last_logits is [max_batch, vocab] = "
                f"{(self.max_batch, self.cfg.vocab_size)}, got "
                f"{logits.shape}")
        self._land()
        self._logits = logits
        self._fresh.clear()
        self._next_ids = np.argmax(logits, -1).astype(np.int32)

    def _decide_on_host(self, row: int, logits: np.ndarray) -> None:
        """`row`'s pending id from logits the host already holds."""
        self._fresh[row] = logits
        self._next_ids[row] = np.argmax(logits)

    # -- request intake ------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               req_id: Optional[int] = None,
               slo_class: str = "standard") -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new_tokens > self.max_seq_tokens:
            raise InvalidRequestError(
                f"request needs {prompt.size} + {max_new_tokens} "
                f"tokens > per-sequence budget {self.max_seq_tokens}")
        if req_id is None:
            req_id = self._next_req_id
        self._next_req_id = max(self._next_req_id, req_id) + 1
        req = Request(req_id=req_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      arrival_step=self.step_no, slo_class=slo_class)
        self._submit_wall[req_id] = time.perf_counter()
        self._req_obs[req_id] = {
            "prefill_end": None, "first": False, "spec_ms": 0.0}
        tl = get_timeline()
        if tl is not None:
            tl.instant("serve_submit", category="serve",
                       args={"req": req_id,
                             "prompt_tokens": int(prompt.size),
                             "max_new": int(max_new_tokens)},
                       tid=f"req/{req_id}")
        self.sched.submit(req, self.step_no)
        return req_id

    # -- admission -----------------------------------------------------

    def _budget_tokens(self, req: Request) -> int:
        return int(req.prompt.size) + req.max_new_tokens + self._headroom

    def _can_admit(self, req: Request) -> bool:
        n = self._budget_tokens(req)
        return all(c.can_board(n) for c, _ in self._caches)

    def _admit(self) -> int:
        """Board what the scheduler admits; returns how many.  Each
        prefill is timed by one pair of clock reads, which the request's
        `_req_obs` entry, the flight recorder and (through `_finish`)
        the timeline's `decode` span all take."""
        admitted = 0
        for seq in self.sched.admit(self.step_no, self._can_admit):
            admitted += 1
            rid = seq.req.req_id
            obs = self._req_obs.get(rid)
            tl = get_timeline()
            t_submit = self._submit_wall.get(rid)
            t_start = time.perf_counter()
            queue_wait = t_start - t_submit if t_submit is not None else 0.0
            if t_submit is not None and _met.enabled():
                _met.serve_queue_delay.observe(queue_wait)
            if tl is not None and t_submit is not None:
                # queue_wait ends exactly where prefill starts: the
                # request's three spans abut and their durations sum to
                # its e2e latency.
                tl.complete("queue_wait", category="serve",
                            start_us=tl.now_us(t_submit),
                            args={"req": rid}, tid=f"req/{rid}")
            budget = self._budget_tokens(seq.req)
            T0 = int(seq.req.prompt.size)
            with span("prefill", "serve",
                      {"req": rid, "prompt_tokens": T0, "row": seq.row,
                       "pages": self.pool.pages_needed(budget),
                       "scratch_pages": self.pool.scratch_pages(T0, budget),
                       "queue_wait_us": round(queue_wait * 1e6, 1)},
                      tid=f"req/{rid}"):
                lg = [c.board(rid, seq.row, budget, p, seq.req.prompt,
                              _prefill_fn(c.cfg))
                      for c, p in self._caches][0]
                first_logits = np.asarray(lg)[0]   # waits for the prefill
            t_end = time.perf_counter()
            if obs is not None:
                obs["prefill_end"] = t_end
            if self.flightrec is not None:
                self.flightrec.record(
                    "span", {"name": "prefill", "req": rid,
                             "prompt_tokens": T0, "row": seq.row},
                    step=self.step_no,
                    ts_us=self.flightrec.now_us(t_start),
                    dur_us=(t_end - t_start) * 1e6)
            seq.pos = T0
            self.row_pos[seq.row] = T0
            self._decide_on_host(seq.row, first_logits)
        return admitted

    def _first_token(self, seq: ActiveSeq) -> None:
        """Called once per request, right after its first token is
        decided — TTFT = queue wait + prefill + the first decode
        dispatch, measured from submit."""
        rid = seq.req.req_id
        obs = self._req_obs.get(rid)
        if obs is None or obs["first"]:
            return
        obs["first"] = True
        t0 = self._submit_wall.get(rid)
        if t0 is not None and _met.enabled():
            _met.serve_ttft.observe(time.perf_counter() - t0)
        tl = get_timeline()
        if tl is not None:
            tl.instant("serve_first_token", category="serve",
                       args={"req": rid, "step": self.step_no},
                       tid=f"req/{rid}")
        if self.flightrec is not None:
            self.flightrec.record("first_token", {"req": rid},
                                  step=self.step_no)

    def _finish(self, seq: ActiveSeq) -> None:
        rid = seq.req.req_id
        self.sched.evict(self.step_no, seq.row)
        for cache, _ in self._caches:
            cache.release(rid, seq.row)
        self.row_pos[seq.row] = 0
        now = time.perf_counter()
        t0 = self._submit_wall.pop(rid, None)
        if t0 is not None:
            self.request_latencies_ms.append((now - t0) * 1e3)
            if _met.enabled():
                _met.serve_e2e_latency.observe(now - t0)
        obs = self._req_obs.pop(rid, None)
        t_decode = obs["prefill_end"] if obs is not None else None
        tl = get_timeline()
        if tl is not None:
            if t_decode is not None:
                tl.complete("decode", category="serve",
                            start_us=tl.now_us(t_decode),
                            args={"req": rid,
                                  "tokens": len(seq.generated),
                                  "spec_ms": round(obs["spec_ms"], 3)},
                            tid=f"req/{rid}")
            tl.instant("serve_evict", category="serve",
                       args={"req": rid,
                             "tokens": len(seq.generated)},
                       tid=f"req/{rid}")
        if self.flightrec is not None and t_decode is not None:
            self.flightrec.record(
                "span", {"name": "decode", "req": rid,
                         "tokens": len(seq.generated)},
                step=self.step_no,
                ts_us=self.flightrec.now_us(t_decode),
                dur_us=(now - t_decode) * 1e6)

    # -- the step ------------------------------------------------------

    def step(self) -> List[ActiveSeq]:
        """One scheduler+decode iteration; returns sequences finished
        THIS step (their ``generated`` lists are complete).

        A crash inside the step — including ``PoolExhaustedError`` —
        lands the step in flight (so whoever catches finds every id on
        the host) and dumps the flight recorder BEFORE the exception
        propagates, so the post-mortem ring always covers the failing
        step."""
        try:
            return self._step_impl()
        except BaseException as e:
            with contextlib.suppress(Exception):    # the device's crash
                self._land()
            if self.flightrec is not None:
                reason = ("pool_exhausted"
                          if isinstance(e, PoolExhaustedError)
                          else f"crash:{type(e).__name__}")
                self.flightrec.record(
                    "error", {"type": type(e).__name__,
                              "msg": str(e)[:200]}, step=self.step_no)
                self.flightrec.dump(reason)
            raise

    def _step_impl(self) -> List[ActiveSeq]:
        with span("step", "serve",
                  {"step": self.step_no,
                   "queued": self.sched.queue_depth(),
                   "active": len(self.sched.active)}):
            finished = self._step_phases()
        self.step_no += 1
        return finished

    def _step_phases(self) -> List[ActiveSeq]:
        t0 = time.perf_counter()
        with span("admit", "serve"):
            admitted = self._admit()
        self._synced = {}
        # A speculative round reads and decides on the host: it starts
        # from every id there.
        spec = (self.draft_params is not None and bool(self.sched.active)
                and (self.force_spec or self.slo.update(self.step_no)))
        if spec:
            self._land()
        feed = np.zeros(self.max_batch, np.int64)
        rows: List[int] = []
        with span("sample", "serve"):
            for row in sorted(self.sched.active):
                seq = self.sched.active[row]
                if self._next_ids[row] < 0:    # the step in flight's
                    if len(seq.generated) + 1 >= seq.req.max_new_tokens:
                        continue    # ends by count once its id lands
                    feed[row] = -1
                elif self._emit(seq):
                    continue
                else:
                    feed[row] = seq.generated[-1]
                rows.append(row)
        decided = 0
        if not rows:
            self._land(emit=True)   # nothing to dispatch before the sync
        elif spec:
            t_spec = time.perf_counter()
            with span("launch", "serve"):
                for cache, _ in self._caches:
                    cache.refresh()
                decided = self._spec_round(rows, feed)
            spec_ms = (time.perf_counter() - t_spec) * 1e3
            for r in rows:
                ob = self._req_obs.get(self.sched.active[r].req.req_id)
                if ob is not None:
                    ob["spec_ms"] += spec_ms
            self.spec_steps += 1
            self._count_step(len(rows))
        else:
            self._plain_step(rows, feed)
        finished, self._ended = self._ended, []
        counts = {"rows": len(rows), "admitted": admitted,
                  "finished": len(finished), "decided": 1 + decided}
        with span("observe", "serve",
                  {"step": self.step_no, **counts, **self._synced}):
            if rows:
                dt_ms = (time.perf_counter() - t0) * 1e3
                per_tok = dt_ms / (1 + decided)
                self.token_latencies_ms.append(per_tok)
                self.slo.record(per_tok)
                if _met.enabled():
                    _met.serve_intertoken.observe(per_tok / 1e3)
            self._update_gauges()
            if self.flightrec is not None:
                self.flightrec.record(
                    "step", {**counts, "steps_ahead": self.steps_ahead,
                             "rows_dropped": self.rows_dropped},
                    step=self.step_no)
        return finished

    def _count_step(self, rows: int) -> None:
        """A device step over `rows` rows whose ids are on the host.
        The server's counters count those: with a step in flight
        `device_steps`, `occupancy_sum` and the routing sums all stand
        one step behind the dispatches, together."""
        self.device_steps += 1
        self.occupancy_sum += rows / self.max_batch

    def _emit(self, seq: ActiveSeq) -> bool:
        """`seq` emits its pending id, which the host holds; True where
        that ends it: it is evicted and its row released."""
        seq.generated.append(int(self._next_ids[seq.row]))
        self._next_ids[seq.row] = -1    # its next: the coming step's
        self.tokens_out += 1
        if len(seq.generated) == 1:
            self._first_token(seq)
        if seq.done:
            self._ended.append(seq)
            self._finish(seq)
        return seq.done

    def _plain_step(self, rows: Sequence[int], feed: np.ndarray) -> None:
        """Dispatch one decode step over `rows`, then land the step
        before it.  `launch` (dispatches only) says what work the step
        is: `dstep`, the ordinal of this device step (`device_steps`,
        and one more where the step before is still in flight: it is
        counted when it lands); `rows` and `rows_pct`, the rows stepped, and
        of `max_batch`; `live_tokens`, the positions the step reads up
        to, summed over them, each row's own new token counted
        (`pos + 1`: what `seq.pos` reads AFTER the step); what the cache
        alone knows (`DecodeCache.step_args`); and `ahead`, 1 where the
        ids of the step before have not reached the host: a row fed -1
        takes its token from them on the device (`_serve_step_fn`).
        Inside it `put` is the view's refresh and the step's inputs put
        on the device, `write_through` the carrying of the new slots to
        where they are kept; the rest of `launch` is the dispatch.  The
        host needs no id for any of it: every stepped row advances by
        one."""
        rows = list(rows)
        base = np.zeros_like(self.row_pos)  # a row left out idles: 0
        base[rows] = self.row_pos[rows]
        ahead = int(self._flight is not None)
        work = {"dstep": self.device_steps + ahead, "rows": len(rows),
                "rows_pct": round(100.0 * len(rows) / self.max_batch, 2),
                "live_tokens": int(base.sum()) + len(rows),  # idle: 0
                **self.pool.step_args(base),
                "ahead": ahead}
        with span("launch", "serve", work):
            with span("put", "serve"):
                for cache, _ in self._caches:
                    cache.refresh()
                lent = self.pool.lend(base)
                fed = jnp.asarray(feed, jnp.int32)
            self._logits, self._ids, cache = _serve_step_fn(self.cfg)(
                self.params, lent, fed, self._ids)
            del lent, fed       # as temporaries would: let go of here
            self._ids.copy_to_host_async()
            self._fresh.clear()
            self.pool.take_back(cache)
            with span("write_through", "serve"):
                self.pool.write_through(rows, base)
        for r in rows:
            self.row_pos[r] += 1
            self.sched.active[r].pos = int(self.row_pos[r])
        self.steps_ahead += ahead
        self._land(emit=True, ahead={
            "dstep": work["dstep"], "rows": len(rows), "ids": self._ids,
            "live": rows})

    def _land(self, emit: bool = False, ahead: Optional[Dict] = None
              ) -> None:
        """Wait for the ids of the step in flight, if one is: `fetch`,
        the one sync of a step (the ids, not the logits they came from;
        behind them, a patterned model's routing counts a layer), after
        which `ahead`, the step just dispatched behind it, is the one in
        flight.  What the sync brought is left for `observe`
        (`_synced`).  The ids become the rows' pending ids on the host,
        for the next `sample`; the iteration that would have sampled
        them (`emit`) emits them here, and a row they end by EOS leaves
        `ahead`, which stepped it once more: that id is dropped when it
        lands and the row's position taken back."""
        flight, self._flight = self._flight, ahead
        if flight is None:
            return
        with span("fetch", "serve", {"bytes": flight["ids"].nbytes}):
            got = np.asarray(flight["ids"])
        self._count_step(flight["rows"])
        routed = got[self.max_batch:].reshape(-1, len(ROUTED))
        self._synced = {"dstep": flight["dstep"]}
        if len(routed):
            hit, fullest, here = routed.sum(axis=0).tolist()  # `ROUTED`
            self.experts_hit_sum += hit
            self.expert_load_max_sum += fullest
            self.pairs_here_sum += here
            self.moe_layer_steps += len(routed)
            self._synced.update(experts_hit=hit, pairs_here=here)
        for r in flight["live"]:
            seq = self.sched.active[r]
            self._next_ids[r] = got[r]
            if emit and self._emit(seq) and ahead is not None \
                    and r in ahead["live"]:
                ahead["live"].remove(r)
                seq.pos -= 1
                self.rows_dropped += 1

    def _spec_round(self, rows: Sequence[int], feed: np.ndarray) -> int:
        """Draft-propose / chunk-verify round; returns how many EXTRA
        tokens (beyond the step's emit) were decided per row."""
        gamma = self.gamma
        base = self.row_pos.copy()
        dstep = _spec_step_fn(self.draft_cfg)
        dcache = self.dpool.lend(base)
        drafts: List[np.ndarray] = []     # d_1 .. d_gamma, each [B]
        cur = feed
        for _ in range(gamma):
            dlg, dcache = dstep(self.draft_params, dcache,
                                jnp.asarray(cur, jnp.int32))
            cur = np.asarray(jnp.argmax(dlg, -1))
            drafts.append(cur)
        self.dpool.take_back(dcache)

        chunk = np.stack([feed] + drafts[:-1], axis=1)     # [B, gamma]
        tlg, tcache = _spec_extend_fn(self.cfg)(
            self.params, self.pool.lend(base),
            jnp.asarray(chunk, jnp.int32))
        self.pool.take_back(tcache)
        tlogits = np.asarray(tlg)                          # [B, g, V]

        # Accepted prefix per row, capped at gamma-1 so the round
        # always ends holding VERIFIED logits for the next undecided
        # position (tlogits[:, n_acc]).  Min-acceptance keeps every
        # row's advance equal; a row that accepted further replays its
        # own draft from those logits next step — values are exact.
        n_acc = gamma - 1
        for r in rows:
            acc = 0
            while acc < gamma - 1 and \
                    int(drafts[acc][r]) == \
                    int(np.argmax(tlogits[r, acc])):
                acc += 1
            n_acc = min(n_acc, acc)
        for r in rows:
            seq = self.sched.active[r]
            for i in range(n_acc):
                if seq.done:
                    break
                seq.generated.append(int(drafts[i][r]))
                self.tokens_out += 1
            self._decide_on_host(r, tlogits[r, n_acc])
            self.row_pos[r] = int(base[r]) + n_acc + 1
            seq.pos = int(self.row_pos[r])
        # Carry the verified slots through (emit token + accepted
        # drafts): positions base .. base + n_acc per row.
        for off in range(n_acc + 1):
            for cache, _ in self._caches:
                cache.write_through(rows, base + off)
        return n_acc

    # -- loops / observability -----------------------------------------

    def run(self, max_steps: int = 100000) -> List[ActiveSeq]:
        """Step until queue and batch drain; returns finished seqs in
        completion order."""
        done: List[ActiveSeq] = []
        for _ in range(max_steps):
            if self.sched.drained():
                break
            done.extend(self.step())
        self.flush_metrics()
        if not self.sched.drained():
            raise InvalidRequestError(
                f"server did not drain within {max_steps} steps "
                f"({self.sched.queue_depth()} queued, "
                f"{len(self.sched.active)} active)")
        return done

    def occupancy_mean(self) -> float:
        return self.occupancy_sum / max(1, self.device_steps)

    def oldest_queue_wait_ms(self) -> float:
        """Wall-clock wait of the oldest QUEUED request — the
        autoscaler's head-of-line pressure signal (zero when the queue
        is empty)."""
        now = time.perf_counter()
        waits = [now - self._submit_wall[r.req_id]
                 for r in self.sched.queue
                 if r.req_id in self._submit_wall]
        return max(waits) * 1e3 if waits else 0.0

    def shed_queued(self, n: int,
                    tenant_priority: Optional[Dict[str, int]] = None
                    ) -> List[Request]:
        """Autoscaler degrade rung: drop up to ``n`` queued requests in
        tenant-priority order (scheduler.shed) and release their
        lifecycle state so they never count against latency stats.
        Returns the shed requests for the caller to fail back."""
        shed = self.sched.shed(self.step_no, n, tenant_priority)
        for req in shed:
            self._submit_wall.pop(req.req_id, None)
            self._req_obs.pop(req.req_id, None)
            if self.flightrec is not None:
                self.flightrec.record(
                    "shed", {"req": req.req_id,
                             "slo_class": req.slo_class},
                    step=self.step_no)
        if shed and _met.enabled():
            _met.autoscale_shed.inc(len(shed))
        return shed

    def _update_gauges(self) -> None:
        # Sampled, not per-step: the p99 percentile over the SLO window
        # costs more than a whole decode dispatch on small models.
        if not _met.enabled() \
                or self.step_no % self._metrics_interval:
            return
        self._set_gauges()

    def _set_gauges(self) -> None:
        _met.serve_queue_depth.set(self.sched.queue_depth())
        _met.serve_batch_occupancy.set(self.sched.occupancy())
        self.pool.set_gauges()
        p99 = self.slo.p99_ms()
        if p99:
            _met.serve_p99_ms.set(p99)
        # Error-budget gauges ride the same cadence (the burn-rate
        # signals the autoscaler consumes — docs/TELEMETRY.md).
        self.slo.export_budget()

    def flush_metrics(self) -> None:
        """Unconditional gauge sample — called at drain and atexit so a
        run shorter than ``HOROVOD_SERVE_METRICS_INTERVAL`` steps still
        exports its final state."""
        if _met.enabled():
            self._set_gauges()

    def _on_slo_flip(self, step: int, event: str, p99: float) -> None:
        tl = get_timeline()
        if tl is not None:
            tl.instant("slo_toggle", category="serve",
                       args={"step": step, "event": event,
                             "p99_ms": round(p99, 3)})
        if self.flightrec is not None:
            self.flightrec.record(
                "slo", {"event": event, "p99_ms": round(p99, 3)},
                step=step)
            if event == "spec_on":
                # The SLO just went over budget — snapshot the ring so
                # the breach is diagnosable even if the run recovers.
                self.flightrec.dump("slo_breach")


__all__ = ["InferenceServer"]
