"""Continuous-batching inference server over the paged KV pool.

One compiled decode step of fixed ``max_batch`` rows serves every
in-flight sequence; admission/eviction happens BETWEEN steps (the
scheduler), and sequence KV state lives in the pool (pool.py).  The
decode kernels run UNCHANGED — the only model-side addition is the
vector-``pos`` path in ``models/decode.py``, because continuously
batched rows sit at different depths of the same step.

Step anatomy (``step()``):

  1. admit   — queued requests board free rows; prefill-on-admit runs
               ``transformer_prefill`` into a scratch cache sized
               exactly to the request's page budget, then bulk-writes
               the pages (``scatter_pages``).
  2. emit    — each active row's next token is decided HOST-side from
               its pending logits (greedy serving); finished rows
               (max_new / EOS) evict and free their pages BEFORE any
               device work, so the last token costs no decode step.
  3. gather  — only if membership changed: rebuild the pooled view.
  4. decode  — one vector-pos ``transformer_decode_step`` (plain), or
               one speculative round (draft chain + chunked verify)
               when the SLO controller has flipped speculation on.
               The programs CONSUME the view (donated, updated in
               place): ``view_k`` / ``view_v`` are rebound to what
               they return, and the arrays passed in are gone.
  5. scatter — copy each active row's written ring slot(s) back into
               its pages; the pool stays the source of truth.

A retention model (``cfg.attn_kind == "retention"``) has no pages: its
cache is one fixed state a row, held once, in the view (``StateSlots``,
pool.py).  Admission then needs a free row and nothing else, the
prefill's final state is written into the row's slot of the view
(``state_install``, inside ``admit``), and steps 3 and 5 have nothing to
copy.  Everything else of the step is the same code.

Speculative rounds keep the greedy target chain EXACT: every decided
token is the argmax of target logits computed over a correct prefix
(accepted-prefix min over rows; stale speculative slots are never
readable before they are overwritten — the same always-write-before-
read ring property ``transformer_speculative_generate`` relies on).

The host's side of a step is five spans under one
(`utils/timeline.span`, category ``serve``: ``step`` holding ``admit``
with one ``prefill`` a request, ``sample``, ``launch``, ``fetch``,
``observe``; docs/SERVING.md has the table), so a profiler trace shows
which of them the device waits for.

All host orchestration (clocks, metrics, env) stays OUTSIDE the jitted
programs; the compiled pieces are the same module-cached
``_spec_step_fn`` / ``_spec_extend_fn`` programs the speculative
decoder uses, plus one prefill jit — shapes (max_batch, view ring,
gamma) key the program cache through tracing, which is why the
``serve_page_tokens`` / ``serve_max_batch`` / ``serve_spec_gamma``
autotuner knobs are part of the compiled-shape key (docs/AUTOTUNE.md).
"""

from __future__ import annotations

import atexit
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
from ..models.decode import (
    _spec_extend_fn,
    _spec_step_fn,
    cache_leaves,
    init_decode_cache,
    transformer_prefill,
)
from ..utils import autotune
from ..utils.timeline import get_timeline, span
from .flightrec import FlightRecorder
from .pool import PagedKVPool, PoolExhaustedError, StateSlots
from .scheduler import ActiveSeq, ContinuousScheduler, Request
from .slo import SloController


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg):
    # Consumes the scratch cache like the step programs consume the
    # view (models/decode.py): `_prefill_into` makes it and rebinds it.
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
        self.retention = cfg.attn_kind == "retention"
        if self.retention:
            for what, asked in (
                    ("quantize", quantize is not None),
                    ("draft_params (speculative serving: the verify "
                     "pass needs snapshots of the state)",
                     draft_params is not None)):
                if asked:
                    raise InvalidRequestError(
                        f"{what} is not supported for a retention model "
                        "(attn_kind='retention')")
        # Per-sequence budget: the full ring a request may need.  The
        # gamma headroom mirrors transformer_speculative_generate — a
        # round writes up to gamma slots past the accepted frontier.
        headroom = self.gamma if draft_params is not None else 0
        self.max_seq_tokens = max_seq_tokens + headroom
        self.view_pages = -(-self.max_seq_tokens // self.page_tokens)
        self.view_tokens = self.view_pages * self.page_tokens
        pool_pages = pool_pages or autotune.current_serve_pool_pages() \
            or self.max_batch * self.view_pages
        self.sched = ContinuousScheduler(self.max_batch, policy=policy,
                                         seed=seed)
        if self.retention:
            self.pool = StateSlots(cfg, self.max_batch,
                                   lambda: len(self.sched.active))
        else:
            self.pool = PagedKVPool(cfg, pool_pages, self.page_tokens,
                                    quantize=quantize)
        self.dpool = None
        if draft_params is not None:
            self.dpool = PagedKVPool(draft_cfg, pool_pages,
                                     self.page_tokens)
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
            if not self.retention:     # rows are the scheduler's events
                self.pool.on_event = lambda ev, sid, n, free: \
                    rec.record("pool", {"event": ev, "req": sid,
                                        "pages": n, "free": free},
                               step=self.step_no)
            if self.dpool is not None:
                self.dpool.on_event = lambda ev, sid, n, free: \
                    rec.record("dpool", {"event": ev, "req": sid,
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
        self.last_logits = np.zeros((self.max_batch, V), np.float32)
        self.row_seq: List[Optional[int]] = [None] * self.max_batch
        # The decode view's two stacked leaves: keys and values gathered
        # from the pool, or a retention model's states and normalisers,
        # which live nowhere else.
        self.view_k = self.view_v = None
        if self.retention:
            self.view_k, self.view_v = self.pool.new_view()
        self.dview_k = self.dview_v = None
        self._dirty_rows: Dict[int, int] = {}    # row -> seq_id to refresh
        self.step_no = 0
        self._next_req_id = 0
        self._submit_wall: Dict[int, float] = {}
        # run stats (read by loadgen / the bench)
        self.tokens_out = 0
        self.device_steps = 0
        self.spec_steps = 0
        self.occupancy_sum = 0.0
        self.state_installs = 0
        #: bytes the state view holds (0 for a paged model)
        self.state_bytes = self.pool.state_bytes if self.retention else 0
        self.token_latencies_ms: List[float] = []
        self.request_latencies_ms: List[float] = []

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
        n = int(req.prompt.size) + req.max_new_tokens
        if self.draft_params is not None:
            n += self.gamma
        return n

    def _can_admit(self, req: Request) -> bool:
        if self.retention:
            # A free row is all a state needs, and the scheduler asks
            # only while it has one.
            return True
        n = self._budget_tokens(req)
        if not self.pool.can_alloc(n):
            return False
        return self.dpool is None or self.dpool.can_alloc(n)

    def _prefill_into(self, pool: PagedKVPool, params, cfg, seq,
                      npages: int):
        scratch = init_decode_cache(cfg, 1, npages * self.page_tokens,
                                    quantize=pool.quantize)
        lg, scratch = _prefill_fn(cfg)(
            params, scratch, jnp.asarray(seq.req.prompt[None]))
        pool.scatter_pages(seq.req.req_id, scratch["k"], scratch["v"])
        return lg

    def _prefill_state(self, seq):
        """A retention model's prefill: the prompt's final state goes
        into the row's slot of the view, whole, where the decode steps
        will update it; no page is written."""
        lg, scratch = _prefill_fn(self.cfg)(
            self.params, self.pool.scratch(),
            jnp.asarray(seq.req.prompt[None]))
        with span("state_install", "serve",
                  {"req": seq.req.req_id, "row": seq.row,
                   "bytes": self.pool.row_bytes}):
            self.view_k, self.view_v = self.pool.install(
                (self.view_k, self.view_v), scratch, seq.row)
        self.state_installs += 1
        return lg

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
            pages = 0 if self.retention else self.pool.pages_needed(budget)
            with span("prefill", "serve",
                      {"req": rid, "prompt_tokens": T0, "row": seq.row,
                       "pages": pages,
                       "queue_wait_us": round(queue_wait * 1e6, 1)},
                      tid=f"req/{rid}"):
                if self.retention:
                    lg = self._prefill_state(seq)
                else:
                    pids = self.pool.alloc(rid, budget)
                    lg = self._prefill_into(self.pool, self.params,
                                            self.cfg, seq, len(pids))
                if self.dpool is not None:
                    dpids = self.dpool.alloc(rid, budget)
                    self._prefill_into(self.dpool, self.draft_params,
                                       self.draft_cfg, seq, len(dpids))
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
            self.last_logits[seq.row] = first_logits
            self.row_seq[seq.row] = rid
            self._dirty_rows[seq.row] = rid
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
        if not self.retention:       # a row given back is all there is
            self.pool.free(rid)
        if self.dpool is not None:
            self.dpool.free(rid)
        self.row_seq[seq.row] = None
        self.row_pos[seq.row] = 0
        self._dirty_rows.pop(seq.row, None)
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

    def _refresh_views(self) -> None:
        """Bring the pooled decode view up to date: a full gather the
        first time, then per-admitted-row updates (evicted rows need
        none — see PagedKVPool.gather_rows).  A retention model's view
        is the only copy and `_prefill_state` has already written it."""
        if self.retention:
            self._dirty_rows.clear()
            return
        if self.view_k is None:
            self.view_k, self.view_v = self.pool.gather(
                self.row_seq, self.view_pages)
            if self.dpool is not None:
                self.dview_k, self.dview_v = self.dpool.gather(
                    self.row_seq, self.view_pages)
        elif self._dirty_rows:
            pairs = sorted(self._dirty_rows.items())
            self.view_k, self.view_v = self.pool.gather_rows(
                self.view_k, self.view_v, pairs, self.view_pages)
            if self.dpool is not None:
                self.dview_k, self.dview_v = self.dpool.gather_rows(
                    self.dview_k, self.dview_v, pairs, self.view_pages)
        self._dirty_rows.clear()

    # -- the step ------------------------------------------------------

    def step(self) -> List[ActiveSeq]:
        """One scheduler+decode iteration; returns sequences finished
        THIS step (their ``generated`` lists are complete).

        A crash inside the step — including ``PoolExhaustedError`` —
        dumps the flight recorder BEFORE the exception propagates, so
        the post-mortem ring always covers the failing step."""
        try:
            return self._step_impl()
        except BaseException as e:
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
        finished: List[ActiveSeq] = []
        feed = np.zeros(self.max_batch, np.int64)
        with span("sample", "serve"):
            for row in sorted(self.sched.active):
                seq = self.sched.active[row]
                if not seq.done:
                    tok = int(np.argmax(self.last_logits[row]))
                    seq.generated.append(tok)
                    self.tokens_out += 1
                    feed[row] = tok
                    if len(seq.generated) == 1:
                        self._first_token(seq)
                if seq.done:
                    finished.append(seq)
                    self._finish(seq)
        rows = sorted(self.sched.active)
        decided = 0
        if rows:
            spec = (self.draft_params is not None
                    and (self.force_spec or self.slo.update(self.step_no)))
            if spec:
                t_spec = time.perf_counter()
                with span("launch", "serve"):
                    self._refresh_views()
                    decided = self._spec_round(rows, feed)
                spec_ms = (time.perf_counter() - t_spec) * 1e3
                for r in rows:
                    sid = self.row_seq[r]
                    ob = (self._req_obs.get(sid)
                          if sid is not None else None)
                    if ob is not None:
                        ob["spec_ms"] += spec_ms
                self.spec_steps += 1
            else:
                self._plain_step(rows, feed)
            self.device_steps += 1
            self.occupancy_sum += len(rows) / self.max_batch
        counts = {"rows": len(rows), "admitted": admitted,
                  "finished": len(finished), "decided": 1 + decided}
        with span("observe", "serve", {"step": self.step_no, **counts}):
            if rows:
                dt_ms = (time.perf_counter() - t0) * 1e3
                per_tok = dt_ms / (1 + decided)
                self.token_latencies_ms.append(per_tok)
                self.slo.record(per_tok)
                if _met.enabled():
                    _met.serve_intertoken.observe(per_tok / 1e3)
            self._update_gauges()
            if self.flightrec is not None:
                self.flightrec.record("step", counts, step=self.step_no)
        return finished

    def _plain_step(self, rows: Sequence[int], feed: np.ndarray) -> None:
        with span("launch", "serve"):      # dispatches only, no wait
            self._refresh_views()
            base = self.row_pos.copy()
            ka, kb = cache_leaves(self.cfg)
            cache = {ka: self.view_k, kb: self.view_v,
                     "pos": jnp.asarray(base, jnp.int32)}
            lg, cache = _spec_step_fn(self.cfg)(
                self.params, cache, jnp.asarray(feed, jnp.int32))
            self.view_k, self.view_v = cache[ka], cache[kb]
            if not self.retention:      # a state has no page to copy to
                sids = [self.row_seq[r] for r in rows]
                slots = [int(base[r]) % self.view_tokens for r in rows]
                self.pool.scatter_slots(self.view_k, self.view_v, sids,
                                        rows, slots)
        with span("fetch", "serve"):       # the step's one sync
            self.last_logits = np.array(lg)    # copy: row writes on admit
        for r in rows:
            self.row_pos[r] += 1
            self.sched.active[r].pos = int(self.row_pos[r])

    def _spec_round(self, rows: Sequence[int], feed: np.ndarray) -> int:
        """Draft-propose / chunk-verify round; returns how many EXTRA
        tokens (beyond the step's emit) were decided per row."""
        gamma = self.gamma
        base = self.row_pos.copy()
        dstep = _spec_step_fn(self.draft_cfg)
        dcache = {"k": self.dview_k, "v": self.dview_v,
                  "pos": jnp.asarray(base, jnp.int32)}
        drafts: List[np.ndarray] = []     # d_1 .. d_gamma, each [B]
        cur = feed
        for _ in range(gamma):
            dlg, dcache = dstep(self.draft_params, dcache,
                                jnp.asarray(cur, jnp.int32))
            cur = np.asarray(jnp.argmax(dlg, -1))
            drafts.append(cur)
        self.dview_k, self.dview_v = dcache["k"], dcache["v"]

        chunk = np.stack([feed] + drafts[:-1], axis=1)     # [B, gamma]
        tcache = {"k": self.view_k, "v": self.view_v,
                  "pos": jnp.asarray(base, jnp.int32)}
        tlg, tcache = _spec_extend_fn(self.cfg)(
            self.params, tcache, jnp.asarray(chunk, jnp.int32))
        self.view_k, self.view_v = tcache["k"], tcache["v"]
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
            self.last_logits[r] = tlogits[r, n_acc]
            self.row_pos[r] = int(base[r]) + n_acc + 1
            seq.pos = int(self.row_pos[r])
        # Scatter the verified slots (emit token + accepted drafts):
        # ring positions base .. base + n_acc per row.
        sids = [self.row_seq[r] for r in rows]
        for off in range(n_acc + 1):
            slots = [(int(base[r]) + off) % self.view_tokens
                     for r in rows]
            self.pool.scatter_slots(self.view_k, self.view_v, sids,
                                    rows, slots)
            if self.dpool is not None:
                self.dpool.scatter_slots(self.dview_k, self.dview_v,
                                         sids, rows, slots)
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
        if not self.retention:      # a state has no pages: not exported
            _met.serve_pool_pages_free.set(self.pool.pages_free())
        _met.serve_state_bytes.set(self.state_bytes)
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
