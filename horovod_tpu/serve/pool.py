"""The serving engine's caches: what `InferenceServer` asks of one
(`DecodeCache`), the four that answer it (`PagedKVPool` for keys and
values, `StateSlots` for a retention model's state a row, `WindowedKVPool`
for a patterned model: pages for the layers that see the whole context,
one ring of `window` slots a row for the layers that see a window,
`KindKVPool` for a patterned model whose layers are of ONE kind that sees
the whole context: pages and nothing beside them, of per-head keys and
values or of a latent kind's two leaves of unequal width, one compressed
latent and one shared key a token), and `make_cache`, which picks one
from the model's configuration.

A contiguous decode cache ties a sequence's KV bytes to its batch row
for the whole generation — finished sequences hold pages until the
batch drains.  The paged pool breaks that coupling (the vLLM PagedAttention idea, applied to this repo's
ring-decode cache): one fixed-size page table per model, page =
``page_tokens`` tokens x layers x kv-heads, carved out of the SAME
``init_decode_cache`` storage (so the int8 / fp8_e4m3 quantized layouts
ride along unchanged), with per-sequence page lists and LIFO alloc/free
on admit/evict.

The decode kernels never see pages.  ``gather`` materializes the
active set's pages into a ``[L, B, Hkv, view_tokens, Dh]`` view — the
exact shape ``transformer_decode_step`` already takes — and
``scatter_slots`` copies the one ring slot each step writes back into
the owning page.  The layout is ``init_decode_cache``'s, head-major
(models/decode.py's module text has why: the step's contractions read a
layer's slice of the view where it lies), so a page is
``[L, Hkv, page_tokens, Dh]`` and a row's pages are joined along the
slot axis UNDER each kv head: the gathers swap the page and head axes
before they merge page and offset, ``scatter_pages`` splits a prefilled
ring the same way, and a slot is ``Hkv`` pieces of ``Dh`` numbers.
The gathers run at admission; the write-back runs once a step and
moves ``L x Hkv`` pieces a row, 0.14 to 0.30 ms at the benchmark's
shapes (PERF.md, ``pool_scatter_share.*``), after the step and while
the host fetches its logits.  All of it is pure data movement (no
arithmetic), which is why pooled decode is BITWISE-equal to
contiguous-cache decode: the step consumes identical bytes either way
(tests/test_serve.py::test_pooled_decode_bitwise_equal).

Amortization contract (see docs/SERVING.md): the view is rebuilt only
on MEMBERSHIP change (admit/evict); steady-state steps pay one
written-slot scatter per active row.  The pool stays the source of
truth, so replica handoff and bitwise replay need no view state.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common.exceptions import HorovodTpuError, InvalidRequestError
from ..metrics import catalog as _met
from ..models.decode import cache_leaves, cache_slots, init_decode_cache
from ..ops import decode_attention, retention_step
from ..utils.timeline import span


# -- jitted data-movement kernels -------------------------------------------
# The page-table bookkeeping (free stack, page lists) is host-side
# Python; the data movement is ONE compiled program per shape signature
# (the eager op-by-op versions cost 4-8 dispatches per step, which
# dominated the serving step on small models).  Pool buffers are
# donated: the caller always
# rebinds self.k/self.v to the result, and serving pools are the
# biggest buffers on the chip — double-buffering them per step would
# halve the page budget.


def _each(kv, f):
    """Apply f to a plain cache array or to both halves of a quantized
    {"q", "scale"} dict (payload and scale move together untouched)."""
    if isinstance(kv, dict):
        return {"q": f(kv["q"], False), "scale": f(kv["scale"], True)}
    return f(kv, False)


def _leaf(kv, scale: bool):
    """The array `_each` is visiting: a quantized dict's scale or
    payload, or the plain array itself."""
    return kv["scale" if scale else "q"] if isinstance(kv, dict) else kv


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_pages_jit(pool_kv, idx):
    k, v = pool_kv
    return (_each(k, lambda c, _s: c.at[:, idx].set(0)),
            _each(v, lambda c, _s: c.at[:, idx].set(0)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slots_jit(pool_kv, view_kv, pids, offs, rows, slots):
    k, v = pool_kv
    vk, vv = view_kv

    def one(pool_c, view_c):
        def f(c, scale):
            src = _leaf(view_c, scale)
            # Layers and kv heads ride among the indices on both sides,
            # L x Hkv x n pieces of Dh numbers: as window axes around the
            # slot they make the v5e compiler transpose pool and view
            # whole (models/decode.py `_cache_write_rows`).
            ls = jnp.arange(c.shape[0])[:, None, None]
            hs = jnp.arange(c.shape[2])[None, :, None]
            return c.at[ls, pids, hs, offs].set(src[ls, rows, hs, slots])
        return _each(pool_c, f)

    return one(k, vk), one(v, vv)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _scatter_pages_jit(pool_kv, cache_kv, idx, n_pages):
    k, v = pool_kv
    ck, cv = cache_kv

    def one(pool_c, c):
        def f(pc, scale):
            src = _leaf(c, scale)[:, 0]  # [L, Hkv, ring, ...]
            src = src.reshape(*src.shape[:2], n_pages, -1, *src.shape[3:])
            return pc.at[:, idx].set(jnp.swapaxes(src, 1, 2))
        return _each(pool_c, f)

    return one(k, ck), one(v, cv)


def _join_pages(g):
    """Rows' gathered pages [L, B, Vp, Hkv, pt, ...] as view rows
    [L, B, Hkv, Vp * pt, ...]: a row's pages in order along the slot
    axis, under each kv head."""
    g = jnp.swapaxes(g, 2, 3)
    return g.reshape(*g.shape[:3], -1, *g.shape[5:])


@jax.jit
def _gather_jit(pool_kv, idx):
    k, v = pool_kv
    return (_each(k, lambda c, _s: _join_pages(c[:, idx])),
            _each(v, lambda c, _s: _join_pages(c[:, idx])))


@functools.partial(jax.jit, donate_argnums=(0,))
def _gather_rows_jit(view_kv, pool_kv, idx, rows):
    vk, vv = view_kv
    k, v = pool_kv

    def one(view_c, pool_c):
        def f(vc, scale):
            return vc.at[:, rows].set(
                _join_pages(_leaf(pool_c, scale)[:, idx]))
        return _each(view_c, f)

    return one(vk, k), one(vv, v)


class DecodeCache:
    """What `InferenceServer` asks of a model's cache, all of it:

      - ``pages_needed(n_tokens)``, ``can_board(n_tokens)``: what such a
        request takes, and whether it may board now;
        ``scratch_pages(prompt_tokens, n_tokens)``: how many of those
        pages its boarding prefills (the `prefill` span says it);
      - ``board(req_id, row, n_tokens, params, prompt, prefill)``: run
        the prefill program and leave the row ready for the next step;
        returns the prompt's last logits, not waited for;
      - ``release(req_id, row)``; ``refresh()`` the view before a step;
      - ``lend(pos)`` / ``take_back(cache)``: the ``{leaf, leaf, "pos"}``
        dict a step program takes, and what it returned;
      - ``write_through(rows, positions)``: the view's `rows` were
        stepped at ``positions[row]``; carry that to where it is kept;
      - ``step_args(positions)``: what this cache alone knows of the
        work of a step at these positions (host integers, 0 for an idle
        row), as arguments of the step's `hvd.serve.launch` span: a
        cache with slots gives ``view_read_pct``, the share of the
        view's blocks the step reads, %; a cache of states
        ``state_read_pct``, the share of the rows' states it reads, %;
        a cache with rings of one kind also ``ring_tokens``, the tokens
        live in a layer's ring over the stepped rows;
      - ``utilization()``, ``set_gauges()``, ``state_bytes``,
        ``installs``, and ``on_event``, which a cache with pages calls
        with (event, req_id, n_pages, pages_free).

    The step programs take the view donated and update it in place
    (models/decode.py ``_layer_walk``), so a cache holds ONE view and
    owns it: nobody else keeps an array that a step has consumed.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.leaves = cache_leaves(cfg)
        #: the two stacked leaves; None while lent, and before a paged
        #: cache's first gather
        self.view: Optional[Tuple] = None
        self.on_event: Optional[Callable] = None
        self.state_bytes = self.installs = 0

    def lend(self, pos) -> Dict:
        (ka, a), (kb, b) = zip(self.leaves, self.view)
        self.view = None
        return {ka: a, kb: b, "pos": jnp.asarray(pos, jnp.int32)}

    def take_back(self, cache: Dict) -> None:
        self.view = tuple(cache[n] for n in self.leaves)

    def step_args(self, positions) -> Dict[str, float]:
        return {}

    def set_gauges(self) -> None:
        _met.serve_state_bytes.set(self.state_bytes)


class PoolExhaustedError(HorovodTpuError):
    """Admission asked for more KV pages than the pool has free.  The
    scheduler treats this as back-pressure (the request waits in the
    queue), not as a crash."""


class PagedKVPool(DecodeCache):
    """Fixed-size page table over ``init_decode_cache`` storage.

    Storage layout: ``k``/``v`` are the plain decode-cache arrays with
    the BATCH axis reinterpreted as the PAGE axis —
    ``[L, total_pages, Hkv, page_tokens, Dh]`` (quantized variants are
    the same ``{"q", "scale"}`` dicts).  A sequence's logical ring of
    ``n`` tokens maps to ``ceil(n / page_tokens)`` pages; slot ``s``
    lives at ``(pages[s // page_tokens], s % page_tokens)``.  `rows` and
    `view_pages` size the view `DecodeCache`'s methods keep; a pool
    driven by ``alloc`` / ``gather`` alone needs neither.
    """

    def __init__(self, cfg, total_pages: int, page_tokens: int,
                 quantize: Optional[str] = None, rows: int = 0,
                 view_pages: int = 0):
        super().__init__(cfg)
        if total_pages < 1:
            raise InvalidRequestError(
                f"total_pages must be >= 1, got {total_pages}")
        if page_tokens < 1:
            raise InvalidRequestError(
                f"page_tokens must be >= 1, got {page_tokens}")
        store = init_decode_cache(cfg, total_pages, page_tokens,
                                  quantize=quantize)
        self.k = store["k"]
        self.v = store["v"]
        self.total_pages = total_pages
        self.page_tokens = page_tokens
        self.quantize = quantize
        # LIFO free stack: page 0 at the top so a fresh pool allocates
        # 0, 1, 2, ... — deterministic reuse order for the tests.
        self._free: List[int] = list(range(total_pages - 1, -1, -1))
        self.pages: Dict[int, List[int]] = {}
        self.view_pages = view_pages
        self._row_seq: List[Optional[int]] = [None] * rows
        self._boarded: Dict[int, int] = {}   # row -> req_id, to refresh

    # -- the server's contract (DecodeCache) -----------------------------

    def board(self, req_id, row, n_tokens, params, prompt, prefill):
        return self.board_pages(req_id, row, n_tokens, params, prompt,
                                prefill)[0]

    def scratch_pages(self, prompt_tokens: int, n_tokens: int) -> int:
        """The pages of the scratch cache a boarding prefills: the
        prompt's, whatever is to follow it."""
        return min(self.pages_needed(prompt_tokens),
                   self.pages_needed(n_tokens))

    def board_pages(self, req_id, row, n_tokens, params, prompt, prefill,
                    cfg=None, kind=None) -> Tuple:
        """How every cache with pages boards a request.  The scratch
        cache the prefill fills holds the PROMPT's pages and no slot of
        the output's, so what a boarding compiles (the scratch, the
        prefill, the bulk write) is keyed by the prompt's length alone;
        the budget's other pages are zeroed where they lie.  `cfg` is
        the model's where this pool keeps one `kind` of its layers.
        Returns the prompt's last logits and the prefilled scratch."""
        n = self.scratch_pages(len(prompt), n_tokens)
        self.alloc(req_id, n_tokens, covered=n)
        scratch = init_decode_cache(cfg or self.cfg, 1,
                                    n * self.page_tokens, self.quantize)
        lg, scratch = prefill(params, scratch, jnp.asarray(prompt[None]))
        self.seat(req_id, row, *(
            scratch[leaf] if kind is None else scratch[leaf][kind]
            for leaf in self.leaves))
        return lg, scratch

    def seat(self, req_id, row, cache_k, cache_v) -> None:
        """A prefilled batch-1 cache into the request's pages, and the
        request into `row`, whose slice of the view the next `refresh`
        gathers."""
        self.scatter_pages(req_id, cache_k, cache_v)
        self._row_seq[row] = self._boarded[row] = req_id

    def release(self, req_id, row) -> None:
        self.free(req_id)
        self._row_seq[row] = None
        self._boarded.pop(row, None)

    def refresh(self) -> None:
        """A full gather the first time, then only the rows boarded
        since (evicted rows need none — see `gather_rows`)."""
        if self.view is None:
            self.view = self.gather(self._row_seq, self.view_pages)
        elif self._boarded:
            self.view = self.gather_rows(
                *self.view, sorted(self._boarded.items()), self.view_pages)
        self._boarded.clear()

    def write_through(self, rows, positions) -> None:
        ring = self.view_pages * self.page_tokens
        self.scatter_slots(*self.view, [self._row_seq[r] for r in rows],
                           rows, [int(positions[r]) % ring for r in rows])

    def view_read_pct(self, positions) -> float:
        """What models/decode.py `_decode_layer` reads of the view in a
        step over rows at `positions` (host integers, 0 for an idle
        row): each row's live blocks where the kernel runs
        (ops/decode_attention.py), every slot under the einsum."""
        slots = self.view_pages * self.page_tokens
        if self.quantize is not None or not decode_attention.reads_live(
                slots):
            return 100.0
        return decode_attention.read_pct(positions, slots)

    def step_args(self, positions) -> Dict[str, float]:
        return {"view_read_pct": round(self.view_read_pct(positions), 2)}

    def set_gauges(self) -> None:
        super().set_gauges()
        _met.serve_pool_pages_free.set(self.pages_free())

    # -- accounting ----------------------------------------------------

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_tokens)

    def pages_free(self) -> int:
        return len(self._free)

    def utilization(self) -> float:
        return 1.0 - len(self._free) / self.total_pages

    def can_board(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    # -- alloc / free ---------------------------------------------------

    def alloc(self, seq_id: int, n_tokens: int,
              covered: int = 0) -> List[int]:
        """Allocate enough pages for ``n_tokens`` ring slots and zero
        them, but for the first ``covered``: those the caller's bulk
        write covers whole (`board_pages`: the prompt's, the last one's
        tail with a fresh scratch's zeros).

        Zeroing on alloc, not on free, keeps eviction O(1) and makes a
        freshly gathered view bitwise-equal to a fresh contiguous
        cache — the parity anchor the serve tests pin."""
        if seq_id in self.pages:
            raise InvalidRequestError(
                f"sequence {seq_id} already holds pages "
                f"{self.pages[seq_id]}")
        need = self.pages_needed(n_tokens)
        if need > len(self._free):
            raise PoolExhaustedError(
                f"need {need} pages for {n_tokens} tokens, only "
                f"{len(self._free)}/{self.total_pages} free")
        pids = [self._free.pop() for _ in range(need)]
        if fresh := pids[covered:]:
            # (a numpy list: `jnp.asarray` of a Python one compiles a
            # conversion for every list length)
            self.k, self.v = _zero_pages_jit(
                (self.k, self.v), np.asarray(fresh, np.int32))
        self.pages[seq_id] = pids
        if self.on_event is not None:
            self.on_event("alloc", seq_id, len(pids), len(self._free))
        return pids

    def free(self, seq_id: int) -> List[int]:
        """Return a sequence's pages to the free stack (on evict/EOS)."""
        try:
            pids = self.pages.pop(seq_id)
        except KeyError:
            raise InvalidRequestError(
                f"sequence {seq_id} holds no pages") from None
        # Reversed so the most-recently-used page sits on top and the
        # next alloc reuses it first (cache-warm, deterministic).
        self._free.extend(reversed(pids))
        if self.on_event is not None:
            self.on_event("free", seq_id, len(pids), len(self._free))
        return pids

    # -- view gather / scatter -----------------------------------------

    def gather(self, seq_ids: Sequence[Optional[int]],
               view_pages: int) -> Tuple:
        """Materialize the active rows' pages as a contiguous decode
        view ``[L, B, Hkv, view_pages * page_tokens, Dh]``.

        ``seq_ids[b] is None`` marks an idle row; idle rows (and the
        tail of short page lists) index page 0 — never READ, because
        the ring's absolute-position mask hides slots past each row's
        ``pos``, and never WRITTEN BACK, because ``scatter_slots`` only
        runs over active rows."""
        return _gather_jit((self.k, self.v),
                           self._page_table(seq_ids, view_pages))

    def _page_table(self, seq_ids, view_pages: int) -> jax.Array:
        idx = np.zeros((len(seq_ids), view_pages), np.int32)
        for b, sid in enumerate(seq_ids):
            if sid is None:
                continue
            pids = self.pages[sid]
            if len(pids) > view_pages:
                raise InvalidRequestError(
                    f"sequence {sid} holds {len(pids)} pages > view "
                    f"capacity {view_pages}")
            idx[b, :len(pids)] = pids
        return jnp.asarray(idx)

    def gather_rows(self, view_k, view_v,
                    row_sids: Sequence[Tuple[int, int]],
                    view_pages: int) -> Tuple:
        """Refresh only the given (row, seq_id) pairs of an EXISTING
        view — the admit-time fast path.  Rows whose sequence was
        evicted need no refresh at all (their stale view bytes are
        masked off and never scattered back), so steady-state
        continuous batching pays one small row update per ADMISSION,
        not a full pool gather per membership change."""
        if not row_sids:
            return view_k, view_v
        rows, sids = zip(*row_sids)
        return _gather_rows_jit(
            (view_k, view_v), (self.k, self.v),
            self._page_table(sids, view_pages),
            jnp.asarray(rows, jnp.int32))

    def scatter_slots(self, view_k, view_v, seq_ids: Sequence[int],
                      rows: Sequence[int],
                      slots: Sequence[int]) -> None:
        """Copy ONE written ring slot per active row from the view back
        into the owning page: row ``rows[i]`` (sequence ``seq_ids[i]``)
        wrote view slot ``slots[i]`` this step.  Exact copy — the
        quantized payload and its scale move together untouched."""
        if not seq_ids:
            return
        pt = self.page_tokens
        pids = [self.pages[sid][s // pt] for sid, s in zip(seq_ids, slots)]
        self.k, self.v = _scatter_slots_jit(
            (self.k, self.v), (view_k, view_v),
            *(jnp.asarray(list(a), jnp.int32) for a in (
                pids, [s % pt for s in slots], rows, slots)))

    def scatter_pages(self, seq_id: int, cache_k, cache_v) -> None:
        """Install a freshly prefilled contiguous cache (batch 1, a ring
        of a whole number of pages, the sequence's page budget at most)
        into the FIRST pages of the sequence — the admit-time bulk
        write.  Boarding hands it the prompt's pages (`board_pages`)."""
        pids = self.pages[seq_id]
        pt = self.page_tokens
        ring = cache_slots(cache_k)
        n, tail = divmod(ring, pt)
        if tail or n > len(pids):
            raise InvalidRequestError(
                f"prefill cache ring {ring} is not a whole number of "
                f"pages of {pt} tokens within the page budget "
                f"{len(pids) * pt} of sequence {seq_id}")
        self.k, self.v = _scatter_pages_jit(
            (self.k, self.v), (cache_k, cache_v),
            np.asarray(pids[:n], np.int32), n)


@functools.partial(jax.jit, donate_argnums=(0,))
def _state_install(view, state, row):
    """Write a prefill's final state (batch 1) into slot `row` of the
    decode view's stacked leaves, in the view's own buffers."""
    return tuple(lax.dynamic_update_slice(
        v, s.astype(v.dtype), (0, row) + (0,) * (v.ndim - 2))
        for v, s in zip(view, state))


class StateSlots(DecodeCache):
    """The cache of a retention model (models/decode.py): one fixed state
    a row, whatever the context length, held ONCE, in the view.  It is no
    pool: there are no pages to count, allocate or free, nothing to
    gather into the view and nothing to scatter back out of it.  What
    admission needs is a free row, and the scheduler counts rows
    (`rows_held` is its count, so that `utilization` answers from the
    one place that knows).  A prefill's final state is written into its
    row's slot whole, so a row reused after another request carries
    nothing over.  What needs a slot a token is refused here, and the
    free-pages gauge is not written (0 there reads as a stall)."""

    def __init__(self, cfg, rows: int, rows_held: Callable[[], int],
                 quantize: Optional[str] = None, speculative: bool = False):
        for what, asked in (
                ("quantize", quantize is not None),
                ("draft_params (speculative serving: the verify pass "
                 "needs snapshots of the state)", speculative)):
            if asked:
                raise InvalidRequestError(
                    f"{what} is not supported for a retention model "
                    "(attn_kind='retention')")
        super().__init__(cfg)
        self.rows = rows
        self.rows_held = rows_held
        view = init_decode_cache(cfg, rows, 1)
        self.view = tuple(view[n] for n in self.leaves)
        #: bytes of all rows' states and normalisers, and of one row's
        self.state_bytes = sum(a.nbytes for a in self.view)
        self.row_bytes = self.state_bytes // rows
        #: does the step's kernel make the pass over these leaves?
        self.kernel = retention_step.takes(self.view[0])

    def pages_needed(self, n_tokens: int) -> int:
        return 0

    def scratch_pages(self, prompt_tokens: int, n_tokens: int) -> int:
        return 0

    def can_board(self, n_tokens: int) -> bool:
        return True     # the scheduler asks only while it has a free row

    def board(self, req_id, row, n_tokens, params, prompt, prefill):
        lg, scratch = prefill(params, init_decode_cache(self.cfg, 1, 1),
                              jnp.asarray(prompt[None]))
        with span("state_install", "serve",
                  {"req": req_id, "row": row, "bytes": self.row_bytes}):
            self.view = _state_install(
                self.view, tuple(scratch[n] for n in self.leaves),
                jnp.int32(row))
        self.installs += 1
        return lg

    # the row given back is all there is, and the view the only copy
    release = refresh = write_through = lambda self, *a: None

    def state_read_pct(self, positions) -> float:
        """What models/decode.py `_retention_decode_layer` reads of the
        rows' states in a step over rows at `positions` (host integers,
        0 for an idle row): the live rows' where the kernel makes the
        pass (ops/retention_step.py), every row's under the einsums."""
        if not self.kernel:
            return 100.0
        return retention_step.read_pct(positions)

    def step_args(self, positions) -> Dict[str, float]:
        return {"state_read_pct": round(self.state_read_pct(positions), 2)}

    def utilization(self) -> float:
        """Rows held over rows: what the pool's page share is for a
        paged model (the autoscaler's signal)."""
        return self.rows_held() / self.rows


class WindowedKVPool(DecodeCache):
    """The cache of a patterned model (models/decode.py, `_empty_pattern`):
    two kinds of cache under one manager.

    The layers without a window keep what `PagedKVPool` keeps, in a pool
    of their own over those layers alone (`pool`): pages, a gathered view,
    a slot written through a step.  Admission counts THEIR pages and
    nothing else.  The layers with a window keep one ring of `window`
    slots a row and layer, written at ``pos % window``; like a retention
    model's state it is held ONCE (`rings`, beside the pool's view),
    never paged, never gathered and never written back: boarding writes
    the ring a prefill left (a prompt's last `window` tokens) into its
    row's slot whole, so a row reused after another request carries
    nothing over.  Quantized layouts and speculation are refused here
    (known gaps, by name)."""

    def __init__(self, cfg, total_pages: int, page_tokens: int,
                 quantize: Optional[str] = None, rows: int = 0,
                 view_pages: int = 0, speculative: bool = False):
        for what, asked in (
                ("quantize", quantize is not None),
                ("draft_params (speculative serving: the verify pass "
                 "extends a window layer's ring by a chunk)", speculative)):
            if asked:
                raise InvalidRequestError(
                    f"{what} is not supported for a model with a layer "
                    "pattern (layer_attn)")
        super().__init__(cfg)
        windows = {t: cfg.kind_cfg(t).attn_window for t in cfg.attn_kinds()}
        self.ringed = tuple(t for t, w in windows.items() if w)
        #: the rings' length where they are of one kind (`step_args`)
        self.ring_window = (windows[self.ringed[0]]
                            if len(self.ringed) == 1 else None)
        paged = [t for t, w in windows.items() if not w]
        if len(paged) != 1:
            raise InvalidRequestError(
                f"a patterned model is served with one kind of layer "
                f"without a window (its pages), got {paged}")
        self.paged = paged[0]
        self.pool = PagedKVPool(cfg.kind_cfg(self.paged), total_pages,
                                page_tokens, None, rows, view_pages)
        self.pool.on_event = lambda *a: self.on_event and self.on_event(*a)
        empty = init_decode_cache(cfg, rows, 1)
        #: a leaf each, the window layers' rings by kind; None while lent
        self.rings: Optional[Tuple] = tuple(
            {t: empty[n][t] for t in self.ringed} for n in self.leaves)
        #: bytes held by kind (the pages' view comes with its first gather)
        self.ring_bytes = sum(a.nbytes for r in self.rings
                              for a in r.values())
        self.page_bytes = self.pool.k.nbytes + self.pool.v.nbytes
        # what only the pages answer
        for name in ("total_pages", "page_tokens", "pages_needed",
                     "scratch_pages", "pages_free", "can_board",
                     "utilization", "release", "refresh", "write_through"):
            setattr(self, name, getattr(self.pool, name))

    def board(self, req_id, row, n_tokens, params, prompt, prefill):
        lg, scratch = self.pool.board_pages(
            req_id, row, n_tokens, params, prompt, prefill,
            cfg=self.cfg, kind=self.paged)
        self.rings = tuple(
            dict(zip(self.ringed, _state_install(
                tuple(ring[t] for t in self.ringed),
                tuple(scratch[n][t] for t in self.ringed),
                jnp.int32(row))))
            for ring, n in zip(self.rings, self.leaves))
        self.installs += 1
        return lg

    def lend(self, pos) -> Dict:
        full, rings = self.pool.view, self.rings
        self.pool.view = self.rings = None
        return {**{n: {self.paged: f, **r}
                   for n, f, r in zip(self.leaves, full, rings)},
                "pos": jnp.asarray(pos, jnp.int32)}

    def take_back(self, cache: Dict) -> None:
        self.pool.view = tuple(cache[n][self.paged] for n in self.leaves)
        self.rings = tuple({t: cache[n][t] for t in self.ringed}
                           for n in self.leaves)

    def step_args(self, positions) -> Dict[str, float]:
        """The pages' share of the view, and `ring_tokens`: the tokens
        live in a window layer's ring over the rows stepped (those not
        at 0), the step's own among them: ``min(pos + 1, window)``
        summed over the rows.  A model with rings of several kinds does
        not say it: one number would not tell them apart."""
        args = self.pool.step_args(positions)
        if self.ring_window:
            pos = np.asarray(positions)        # min(pos, w - 1) + 1 each
            args["ring_tokens"] = (
                int(np.minimum(pos, self.ring_window - 1).sum())
                + int(np.count_nonzero(pos)))
        return args

    def set_gauges(self) -> None:
        self.pool.set_gauges()
        view = sum(a.nbytes for a in self.pool.view or ())
        _met.serve_cache_bytes.labels("pages").set(self.page_bytes + view)
        _met.serve_cache_bytes.labels("rings").set(self.ring_bytes)


class KindKVPool(PagedKVPool):
    """The cache of a patterned model whose attention layers are all of
    ONE kind that sees the whole context: `PagedKVPool` over that kind's
    layers (`cfg.kind_cfg(kind)`), with no ring beside it.  The pages,
    the view, the scatters and the gathers are the parent's, whatever the
    two leaves' widths: a latent kind's (models/decode.py, "Latent
    attention") are pages of `[1, page_tokens, kv_rank]` and
    `[1, page_tokens, key_lanes]`, one head of each.  What is its own is
    the door to the step programs, which take a patterned model's leaves
    by kind, and the prefill, which is the model's.  Quantized layouts
    and speculation are refused here, by the kind's name."""

    def __init__(self, cfg, total_pages: int, page_tokens: int,
                 quantize: Optional[str] = None, rows: int = 0,
                 view_pages: int = 0, speculative: bool = False):
        (self.kind,) = cfg.attn_kinds()
        self.latent = bool(cfg.latent_kinds())
        named = (f"a latent kind of attention layer (a LatentSpec: "
                 f"{self.kind})" if self.latent
                 else "a model with a layer pattern (layer_attn)")
        for what, asked in (
                ("quantize", quantize is not None),
                ("draft_params (speculative serving: the verify pass "
                 "extends the cache by a chunk)", speculative)):
            if asked:
                raise InvalidRequestError(
                    f"{what} is not supported for {named}")
        super().__init__(cfg.kind_cfg(self.kind), total_pages, page_tokens,
                         None, rows, view_pages)
        self.cfg = cfg          # the model's: what the prefill is built of
        self.page_bytes = self.k.nbytes + self.v.nbytes

    def board(self, req_id, row, n_tokens, params, prompt, prefill):
        return self.board_pages(req_id, row, n_tokens, params, prompt,
                                prefill, kind=self.kind)[0]

    def lend(self, pos) -> Dict:
        cache = super().lend(pos)
        return {**{n: {self.kind: cache[n]} for n in self.leaves},
                "pos": cache["pos"]}

    def take_back(self, cache: Dict) -> None:
        self.view = tuple(cache[n][self.kind] for n in self.leaves)

    def set_gauges(self) -> None:
        super().set_gauges()
        view = sum(a.nbytes for a in self.view or ())
        _met.serve_cache_bytes.labels(
            "latent" if self.latent else "pages").set(self.page_bytes + view)


def make_cache(cfg, *, rows, view_pages, page_tokens, pool_pages,
               quantize, speculative, rows_held) -> DecodeCache:
    """The `DecodeCache` of a model of this configuration: the one place
    under serve/ that knows which kind of attention has which cache."""
    if cfg.attn_kind == "retention":
        return StateSlots(cfg, rows, rows_held, quantize, speculative)
    if cfg.patterned:
        windows = [cfg.kind_cfg(t).attn_window for t in cfg.attn_kinds()]
        pool = KindKVPool if windows == [0] else WindowedKVPool
        return pool(cfg, pool_pages, page_tokens, quantize, rows,
                    view_pages, speculative)
    return PagedKVPool(cfg, pool_pages, page_tokens, quantize, rows,
                       view_pages)


__all__ = ["DecodeCache", "KindKVPool", "PagedKVPool",
           "PoolExhaustedError", "StateSlots", "WindowedKVPool",
           "make_cache"]
