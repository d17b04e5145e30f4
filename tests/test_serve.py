"""Serving-stack tests (horovod_tpu/serve): paged KV pool invariants,
pooled-vs-contiguous bitwise parity, scheduler determinism, the SLO
controller's replayable control trace, input validation, the bench
record stale gate, and the two-replica elastic e2e (a replica dies
mid-stream, lease/respawn recovers every sequence token-exactly)."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common.exceptions import HorovodTpuError
from horovod_tpu.models import (
    TransformerConfig,
    init_decode_cache,
    transformer_decode_step,
    transformer_generate,
    transformer_init,
    transformer_prefill,
)
from horovod_tpu.serve import (
    ContinuousScheduler,
    InferenceServer,
    PagedKVPool,
    PoolExhaustedError,
    Request,
    SloController,
)
from horovod_tpu.serve.loadgen import (
    append_record,
    make_trace,
    read_latest_record,
    run_trace,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                d_ff=64, n_layers=2, compute_dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, transformer_init(jax.random.PRNGKey(0), cfg)


class TestPagedKVPool:
    def test_alloc_free_reuse_no_leak(self):
        pool = PagedKVPool(_cfg(), total_pages=8, page_tokens=4)
        a = pool.alloc(1, 10)          # 3 pages
        b = pool.alloc(2, 4)           # 1 page
        assert a == [0, 1, 2] and b == [3]
        assert pool.pages_free() == 4
        assert pool.utilization() == pytest.approx(0.5)
        pool.free(1)
        assert pool.pages_free() == 7
        # Deterministic LIFO reuse: the MRU page of the freed list
        # comes back first.
        c = pool.alloc(3, 8)
        assert c == [0, 1]
        pool.free(2)
        pool.free(3)
        assert pool.pages_free() == 8
        assert pool.pages == {}        # no leaked page lists

    def test_exhaustion_and_double_alloc(self):
        pool = PagedKVPool(_cfg(), total_pages=2, page_tokens=4)
        pool.alloc(1, 8)
        with pytest.raises(PoolExhaustedError):
            pool.alloc(2, 4)
        with pytest.raises(HorovodTpuError, match="already holds"):
            pool.alloc(1, 4)
        with pytest.raises(HorovodTpuError, match="holds no pages"):
            pool.free(99)
        assert pool.can_board(4) is False
        pool.free(1)
        assert pool.can_board(8) is True

    @pytest.mark.parametrize("quantize", [None, "int8"])
    def test_pooled_decode_bitwise_equal(self, model, quantize):
        """The tentpole parity claim: decode over a pooled-page view is
        BITWISE equal to decode over a contiguous cache, because
        gather/scatter is pure data movement.  Both sides start from
        the SAME per-row prefill bytes (a batched prefill may reduce in
        a different order); the pooled side routes them through
        scatter_pages -> gather."""
        cfg, params = model
        B, T0, steps, ring = 2, 4, 5, 16
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (B, T0), 0, 64), np.int32)

        def _cat(a, b):                 # concat caches on the batch axis
            if isinstance(a, dict):
                return {k: jnp.concatenate([a[k], b[k]], axis=1)
                        for k in a}
            return jnp.concatenate([a, b], axis=1)

        pool = PagedKVPool(cfg, total_pages=2 * (ring // 4),
                           page_tokens=4, quantize=quantize)
        ck = cv = lg0 = None
        for b in range(B):
            pool.alloc(b, ring)
            scratch = init_decode_cache(cfg, 1, ring, quantize=quantize)
            plg, scratch = transformer_prefill(
                params, scratch, jnp.asarray(toks[b:b + 1]), cfg)
            pool.scatter_pages(b, scratch["k"], scratch["v"])
            ck = scratch["k"] if ck is None else _cat(ck, scratch["k"])
            cv = scratch["v"] if cv is None else _cat(cv, scratch["v"])
            lg0 = plg if lg0 is None else jnp.concatenate(
                [lg0, plg], axis=0)

        def _np(kv):
            return (np.asarray(kv["q"]) if isinstance(kv, dict)
                    else np.asarray(kv))

        # gather reproduces the installed bytes exactly
        vk, vv = pool.gather([0, 1], ring // 4)
        np.testing.assert_array_equal(_np(vk), _np(ck))
        np.testing.assert_array_equal(_np(vv), _np(cv))

        pos = np.full(B, T0, np.int64)
        tok = jnp.argmax(lg0, -1)
        rtok = tok
        for _ in range(steps):
            p = jnp.asarray(pos, jnp.int32)
            rlg, rc = transformer_decode_step(
                params, {"k": ck, "v": cv, "pos": p}, rtok, cfg)
            ck, cv = rc["k"], rc["v"]
            lg, c = transformer_decode_step(
                params, {"k": vk, "v": vv, "pos": p}, tok, cfg)
            vk, vv = c["k"], c["v"]
            pool.scatter_slots(vk, vv, [0, 1], [0, 1],
                               [int(q) % ring for q in pos])
            np.testing.assert_array_equal(np.asarray(lg),
                                          np.asarray(rlg))
            pos += 1
            tok, rtok = jnp.argmax(lg, -1), jnp.argmax(rlg, -1)
        # The per-step scatter kept the POOL the source of truth: a
        # fresh gather reproduces the contiguous cache bit-for-bit.
        fk, fv = pool.gather([0, 1], ring // 4)
        np.testing.assert_array_equal(_np(fk), _np(ck))
        np.testing.assert_array_equal(_np(fv), _np(cv))

    def test_gather_rows_matches_full_gather(self, model):
        cfg, _ = model
        pool = PagedKVPool(cfg, total_pages=6, page_tokens=4)
        pool.alloc(10, 8)
        pool.alloc(11, 8)
        vk, vv = pool.gather([10, 11, None], 2)
        pool.free(10)
        pool.alloc(12, 8)
        uk, uv = pool.gather_rows(vk, vv, [(2, 12)], 2)
        fk, fv = pool.gather([None, 11, 12], 2)   # row0 stale is fine:
        np.testing.assert_array_equal(            # compare rows 1..2
            np.asarray(uk)[:, 1:], np.asarray(fk)[:, 1:])
        np.testing.assert_array_equal(
            np.asarray(uv)[:, 1:], np.asarray(fv)[:, 1:])

    def test_validation(self):
        with pytest.raises(HorovodTpuError):
            PagedKVPool(_cfg(), total_pages=0, page_tokens=4)
        with pytest.raises(HorovodTpuError):
            PagedKVPool(_cfg(), total_pages=4, page_tokens=0)


class TestScheduler:
    def _run(self, policy, seed=0):
        sched = ContinuousScheduler(3, policy=policy, seed=seed)
        for n in range(8):              # deep queue: policy must choose
            sched.submit(Request(req_id=n, prompt=np.ones(4),
                                 max_new_tokens=2 + n % 3), 0)
        step = 0
        while not sched.drained():
            sched.admit(step, lambda r: True)
            for row, seq in list(sched.active.items()):
                seq.generated.append(0)
                if seq.done:
                    sched.evict(step, row)
            step += 1
        return sched.decision_log

    @pytest.mark.parametrize("policy", ["fifo", "random", "static"])
    def test_scheduler_deterministic(self, policy):
        assert self._run(policy) == self._run(policy)

    def test_seed_changes_random_policy(self):
        assert self._run("random", 0) != self._run("random", 1)

    def test_static_admits_only_empty_batch(self):
        sched = ContinuousScheduler(2, policy="static")
        for i in range(4):
            sched.submit(Request(req_id=i, prompt=np.ones(2),
                                 max_new_tokens=1), 0)
        assert len(sched.admit(0, lambda r: True)) == 2
        assert sched.admit(1, lambda r: True) == []   # batch occupied
        sched.evict(2, 0)
        assert sched.admit(3, lambda r: True) == []   # still one active
        sched.evict(3, 1)
        assert len(sched.admit(4, lambda r: True)) == 2

    def test_backpressure_stops_admission(self):
        sched = ContinuousScheduler(4)
        for i in range(3):
            sched.submit(Request(req_id=i, prompt=np.ones(2),
                                 max_new_tokens=1), 0)
        out = sched.admit(0, lambda r: r.req_id < 1)
        assert [s.req.req_id for s in out] == [0]
        assert sched.queue_depth() == 2


class TestSloController:
    def test_disabled_without_slo(self):
        c = SloController(None)
        c.record(100.0)
        assert c.update(0) is False and c.decisions == []

    def test_toggle_replay(self):
        lat = [1.0] * 20 + [9.0] * 30 + [1.0] * 40

        def replay():
            c = SloController(5.0, window=8, hysteresis=0.5,
                              dwell_steps=4)
            out = []
            for i, ms in enumerate(lat):
                c.record(ms)
                out.append(c.update(i))
            return c.decisions, out

        d1, states = replay()
        d2, _ = replay()
        assert d1 == d2                      # deterministic replay
        events = [e for _, e, _ in d1]
        assert events[:2] == ["spec_on", "spec_off"]
        assert states[25] is True and states[-1] is False

    def test_dwell_blocks_flapping(self):
        c = SloController(5.0, window=4, dwell_steps=100)
        for i, ms in enumerate([9, 9, 9, 1, 1, 1, 9, 9, 9, 1]):
            c.record(float(ms))
            c.update(i)
        assert len(c.decisions) <= 1

    def test_validation(self):
        with pytest.raises(HorovodTpuError):
            SloController(5.0, hysteresis=0.0)
        with pytest.raises(HorovodTpuError):
            SloController(5.0, window=0)


class TestInputValidation:
    """The satellite bugfix: impossible requests raise HorovodTpuError
    (InvalidRequestError also IS-A ValueError for older callers)."""

    def test_init_decode_cache_bad_batch(self, model):
        cfg, _ = model
        with pytest.raises(HorovodTpuError, match="batch"):
            init_decode_cache(cfg, 0, 8)

    def test_generate_bad_args(self, model):
        cfg, params = model
        prompt = jnp.ones((1, 4), jnp.int32)
        with pytest.raises(HorovodTpuError, match="max_new_tokens"):
            transformer_generate(params, cfg, prompt, 0)
        with pytest.raises(HorovodTpuError, match="max_len"):
            transformer_generate(params, cfg, prompt, 4, max_len=2)
        with pytest.raises(HorovodTpuError, match="non-empty"):
            transformer_generate(params, cfg,
                                 jnp.ones((1, 0), jnp.int32), 4)

    def test_prefill_prompt_longer_than_window(self, model):
        cfg, params = model
        cache = init_decode_cache(cfg, 1, 4)
        with pytest.raises(HorovodTpuError, match="max_len"):
            transformer_prefill(params, cache,
                                jnp.ones((1, 8), jnp.int32), cfg)

    def test_server_rejects_oversized_request(self, model):
        cfg, params = model
        srv = InferenceServer(params, cfg, max_seq_tokens=16,
                              max_batch=2, page_tokens=4)
        with pytest.raises(HorovodTpuError, match="budget"):
            srv.submit(np.ones(8, np.int32), 16)
        with pytest.raises(HorovodTpuError, match="policy"):
            InferenceServer(params, cfg, max_seq_tokens=16,
                            max_batch=2, policy="nope")


class TestInferenceServer:
    def test_continuous_matches_generate(self, model):
        """Every request served through the pooled continuous batch
        yields exactly transformer_generate's greedy tokens."""
        cfg, params = model
        srv = InferenceServer(params, cfg, max_seq_tokens=24,
                              max_batch=3, page_tokens=4)
        rng = np.random.RandomState(2)
        reqs = []
        for _ in range(7):
            prompt = rng.randint(0, 64, size=int(rng.choice([3, 5])))
            mn = int(rng.randint(2, 8))
            reqs.append((srv.submit(prompt, mn), prompt, mn))
        by_id = {s.req.req_id: s.generated for s in srv.run()}
        for rid, prompt, mn in reqs:
            ref, _ = transformer_generate(
                params, cfg, jnp.asarray(prompt[None], jnp.int32), mn)
            assert by_id[rid] == np.asarray(ref)[0].tolist()
        assert srv.pool.pages_free() == srv.pool.total_pages

    def test_spec_serving_matches_generate(self, model):
        """Speculative rounds (independent draft) stay greedy-exact."""
        cfg, params = model
        draft = transformer_init(jax.random.PRNGKey(9), cfg)
        srv = InferenceServer(params, cfg, max_seq_tokens=24,
                              max_batch=2, page_tokens=4,
                              draft_params=draft, draft_cfg=cfg,
                              gamma=3, force_spec=True)
        rng = np.random.RandomState(3)
        reqs = []
        for _ in range(4):
            prompt = rng.randint(0, 64, size=4)
            reqs.append((srv.submit(prompt, 6), prompt))
        by_id = {s.req.req_id: s.generated for s in srv.run()}
        assert srv.spec_steps > 0
        for rid, prompt in reqs:
            ref, _ = transformer_generate(
                params, cfg, jnp.asarray(prompt[None], jnp.int32), 6)
            assert by_id[rid] == np.asarray(ref)[0].tolist()

    @pytest.mark.parametrize("spec", [False, True],
                             ids=["plain", "speculative"])
    def test_step_consumes_the_view(self, model, spec):
        """The step's programs take the decode view donated: the arrays
        that were the view before a step are gone after it (the server
        holds pool + ONE view), and the server goes on stepping."""
        cfg, params = model
        kw = {}
        if spec:
            kw = dict(draft_params=transformer_init(
                jax.random.PRNGKey(9), cfg), draft_cfg=cfg, gamma=3,
                force_spec=True)
        srv = InferenceServer(params, cfg, max_seq_tokens=24,
                              max_batch=2, page_tokens=4, **kw)
        prompts = [np.arange(4, dtype=np.int32), np.arange(3, 8,
                                                           dtype=np.int32)]
        rids = [srv.submit(p, 9) for p in prompts]
        srv.step()                      # builds the view, first step
        for _ in range(2):
            held = [srv.view_k, srv.view_v]
            if spec:
                held += [srv.dview_k, srv.dview_v]
            assert not any(a.is_deleted() for a in held)
            srv.step()
            assert all(a.is_deleted() for a in held)
            assert not srv.view_k.is_deleted()
        by_id = {s.req.req_id: s.generated for s in srv.run()}
        for rid, p in zip(rids, prompts):
            ref, _ = transformer_generate(
                params, cfg, jnp.asarray(p[None], jnp.int32), 9)
            assert by_id[rid] == np.asarray(ref)[0].tolist()

    @pytest.mark.parametrize("kind", ["softmax-bf16", "softmax-int8",
                                      "retention"])
    def test_cache_contract(self, kind):
        """What the server asks of its cache (pool.py `DecodeCache`),
        for each cache there is: board, step, give back; a full paged
        cache holds a request back where a state never does; nothing is
        held after the drain; a row reused carries nothing over."""
        paged = kind != "retention"
        cfg = _cfg(compute_dtype=jnp.bfloat16) if paged else \
            _cfg(attn_kind="retention")
        params = transformer_init(jax.random.PRNGKey(4), cfg)
        # two rows, and pages for ONE request of the full budget
        kw = dict(max_seq_tokens=16, max_batch=2, page_tokens=4,
                  pool_pages=4,
                  quantize="int8" if kind == "softmax-int8" else None)
        rng = np.random.RandomState(5)
        a, b, c = (rng.randint(0, 64, size=10) for _ in range(3))

        def tokens(srv, prompt):
            rid = srv.submit(prompt, 6)
            return {s.req.req_id: s.generated for s in srv.run()}[rid]

        srv = InferenceServer(params, cfg, **kw)
        srv.submit(a, 6)
        srv.step()
        srv.submit(b, 6)                # a free row, and no free page
        srv.step()
        assert srv.sched.queue_depth() == (1 if paged else 0)
        assert srv.pool.utilization() == 1.0
        assert len(srv.run()) == 2
        assert srv.pool.utilization() == 0.0
        assert srv.retention != paged
        assert srv.state_installs == (0 if paged else 2)
        assert srv.state_bytes == (
            0 if paged else srv.view_k.nbytes + srv.view_v.nbytes)
        # c boards a row (and pages) that a and b have used
        assert tokens(srv, c) == tokens(InferenceServer(params, cfg, **kw),
                                        c)
        assert srv.pool.utilization() == 0.0

    def test_eos_stops_row(self, model):
        cfg, params = model
        prompt = np.arange(4, dtype=np.int32)
        ref, _ = transformer_generate(
            params, cfg, jnp.asarray(prompt[None]), 8)
        eos = int(np.asarray(ref)[0, 2])
        srv = InferenceServer(params, cfg, max_seq_tokens=16,
                              max_batch=2, page_tokens=4)
        srv.submit(prompt, 8, eos_id=eos)
        (seq,) = srv.run()
        assert seq.generated[-1] == eos and len(seq.generated) <= 3
        assert seq.generated == np.asarray(ref)[
            0, :len(seq.generated)].tolist()


    def test_eos_found_a_step_late_leaves_nothing_behind(self, model):
        """With a step in flight a row's EOS is found after the row was
        stepped once more: that id is dropped (`rows_dropped`), no token
        of it is emitted, its position and pages are taken back, and the
        row's next tenant is exact.  Everything reads as on a server
        that lands every step, which finds the EOS before it steps."""
        cfg, params = model
        rng = np.random.RandomState(23)
        a, b, c = (rng.randint(0, 64, size=4) for _ in range(3))
        ref = {}
        for name, prompt in (("a", a), ("b", b), ("c", c)):
            toks, _ = transformer_generate(
                params, cfg, jnp.asarray(prompt[None], jnp.int32), 12)
            ref[name] = np.asarray(toks)[0].tolist()
        eos = ref["a"][2]
        assert eos not in ref["a"][:2]
        served = {}
        for cls in (InferenceServer, DecidedOnHost):
            srv = cls(params, cfg, max_seq_tokens=16, max_batch=2,
                      page_tokens=4)
            ids = [srv.submit(a, 12, eos_id=eos), srv.submit(b, 12),
                   srv.submit(c, 5)]        # c waits for a's row
            done = {s.req.req_id: s for s in srv.run()}
            got = [done[i] for i in ids]
            assert got[0].generated == ref["a"][:3]
            assert got[1].generated == ref["b"]
            assert got[2].generated == ref["c"][:5]
            assert got[2].row == got[0].row
            # a: its prompt, and the two steps that gave its tokens
            assert [s.pos for s in got] == [4 + 2, 4 + 11, 4 + 4]
            assert not srv.row_pos.any()
            assert srv.pool.pages_free() == srv.pool.total_pages
            served[cls] = (srv.tokens_out, srv.step_no, srv.rows_dropped,
                           srv.device_steps)
        assert served[InferenceServer][:2] == served[DecidedOnHost][:2]
        assert served[InferenceServer][2:] == (
            1, served[DecidedOnHost][3])
        assert served[DecidedOnHost][2] == 0


# -- the greedy pick inside the step program -------------------------------

class DecidedOnHost(InferenceServer):
    """The rule the server had while the whole logits came to the host
    every step: a row's next token is `np.argmax` of its row of
    `last_logits`.  Every step is landed as soon as it is dispatched
    and the ids its program picked are thrown away: `last_logits`,
    assigned what was read, picks them again on the host."""

    def _plain_step(self, rows, feed):
        super()._plain_step(rows, feed)
        self.last_logits = self.last_logits


def _kind_model(kind):
    cfg = _cfg(attn_kind="retention") if kind == "state" else _cfg()
    return cfg, transformer_init(jax.random.PRNGKey(6), cfg)


def _spy_on_logits(monkeypatch, name):
    """Every output [0] (the logits) of the server's program `name`, as
    the device computed it."""
    from horovod_tpu.serve import server as server_mod

    seen, real = [], getattr(server_mod, name)

    def build(cfg):
        def call(*args):
            out = real(cfg)(*args)
            seen.append(np.array(out[0]))
            return out
        return call

    monkeypatch.setattr(server_mod, name, build)
    return seen


@pytest.mark.parametrize("kind", ["paged", "state"])
class TestGreedyIdsOnDevice:
    KW = dict(max_seq_tokens=24, max_batch=3, page_tokens=4)

    def _drive(self, srv, steps=40, prompts=None):
        """Three requests at once, four more boarding in mid-flight as
        rows come free (every row is re-used), outputs of 1 to 9."""
        rng = np.random.RandomState(8)
        plan = [(0, 5), (0, 2), (0, 9), (2, 4), (3, 1), (5, 7), (9, 3)]
        tokens = {}
        for step in range(steps):
            for at, n in plan:
                if at == step:
                    prompt = rng.randint(0, 64, size=rng.randint(3, 9))
                    rid = srv.submit(prompt, n)
                    if prompts is not None:
                        prompts[rid] = prompt
            for seq in srv.step():
                tokens[seq.req.req_id] = list(seq.generated)
        assert srv.sched.drained() and len(tokens) == len(plan)
        return tokens

    def test_tokens_are_those_decided_on_the_host(self, kind):
        """One step kept in flight, each row fed the device's id, gives
        the tokens of a server that lands every step and picks on the
        host, in as many steps; and both give `transformer_generate`'s,
        a request alone."""
        cfg, params = _kind_model(kind)
        srv = InferenceServer(params, cfg, **self.KW)
        host = DecidedOnHost(params, cfg, **self.KW)
        prompts = {}
        tokens = self._drive(srv, prompts=prompts)
        assert tokens == self._drive(host)
        assert (srv.device_steps, srv.step_no) == \
            (host.device_steps, host.step_no)
        assert srv.device_steps > 10
        # nobody asked for the logits; the host's rule asks every token
        assert srv.logit_fetches == 0
        assert host.logit_fetches == host.device_steps
        # the batch never emptied before the end: every step but the
        # first found the one before it in flight; the host's rule none
        assert srv.steps_ahead == srv.device_steps - 1
        assert host.steps_ahead == 0
        assert srv.rows_dropped == host.rows_dropped == 0
        for rid, prompt in prompts.items():
            ref, _ = transformer_generate(
                params, cfg, jnp.asarray(prompt[None], jnp.int32), 9)
            assert tokens[rid] == \
                np.asarray(ref)[0, :len(tokens[rid])].tolist()

    def test_last_logits_is_what_the_ids_were_picked_from(
            self, kind, monkeypatch):
        """Before a step's program runs, `last_logits` holds the last
        program's logits, and the prefill's in a row admitted since;
        after it, the program's own, for every row; it comes from the
        device once a step at most, and only when read."""
        cfg, params = _kind_model(kind)
        steps = _spy_on_logits(monkeypatch, "_serve_step_fn")
        prefills = _spy_on_logits(monkeypatch, "_prefill_fn")
        held = []

        class Watched(InferenceServer):
            def _plain_step(self, rows, feed):
                held.append((list(rows), self.last_logits.copy()))
                super()._plain_step(rows, feed)

        srv = Watched(params, cfg, **self.KW)
        rng = np.random.RandomState(9)
        seen, n_prefills, boarded = set(), 0, 0
        for step, submit in enumerate([2, 0, 0, 1, 1] + [0] * 7):
            for _ in range(submit):
                srv.submit(rng.randint(0, 64, size=5), 4 + step)
            n_steps = len(steps)
            srv.step()
            if len(steps) == n_steps:
                continue
            rows, at_launch = held[-1]
            # rows admitted in this step, in the order of their prefills
            new = [s.row for s in sorted(srv.sched.active.values(),
                                         key=lambda s: s.req.req_id)
                   if s.req.req_id not in seen]
            seen.update(s.req.req_id for s in srv.sched.active.values())
            for r in rows:
                want = prefills[n_prefills + new.index(r)][0] \
                    if r in new else steps[-2][r]
                np.testing.assert_array_equal(at_launch[r], want)
            n_prefills = len(prefills)
            boarded += len(new)
            # two reads after the step and one at the next launch: one
            # fetch from the device
            assert srv.logit_fetches == len(steps) - 1
            np.testing.assert_array_equal(srv.last_logits, steps[-1])
            np.testing.assert_array_equal(srv.last_logits, steps[-1])
            assert srv.logit_fetches == len(steps)
            with pytest.raises(ValueError, match="read-only"):
                srv.last_logits[0] = 0.0
        assert boarded == n_prefills == 4 and len(steps) > 8
        # a request of one token runs no step: its row keeps the prefill's
        srv.run()
        srv.submit(rng.randint(0, 64, size=6), 1)
        (seq,) = srv.step()
        assert len(steps) == len(held)
        np.testing.assert_array_equal(srv.last_logits[seq.row],
                                      prefills[-1][0])
        assert seq.generated == [int(np.argmax(prefills[-1][0]))]

    def test_assigned_logits_move_the_tokens(self, kind):
        """`last_logits` read and assigned between two steps, with a
        step in flight: reading waits for its logits and lands nothing
        (the next step is still dispatched ahead of its ids); assigning
        lands it, so the step after starts from ids the host holds."""
        cfg, params = _kind_model(kind)
        srv = InferenceServer(params, cfg, **self.KW)
        rng = np.random.RandomState(10)
        for _ in range(3):
            srv.submit(rng.randint(0, 64, size=5), 8)
        srv.step()
        srv.step()
        # `device_steps` counts the steps whose ids have landed
        assert (srv.device_steps, srv.steps_ahead) == (1, 1)
        logits = srv.last_logits.copy()
        srv.step()
        assert (srv.device_steps, srv.steps_ahead) == (2, 2)
        logits = srv.last_logits.copy()
        n = {r: len(s.generated) for r, s in srv.sched.active.items()}
        srv.last_logits = -logits        # whole, as the rehearsal does
        np.testing.assert_array_equal(srv.last_logits, -logits)
        # landed, not emitted: the tokens come in the step that follows
        assert srv.device_steps == 3
        assert n == {r: len(s.generated)
                     for r, s in srv.sched.active.items()}
        srv.step()
        assert (srv.device_steps, srv.steps_ahead) == (3, 2)
        assert len(n) == 3
        for r, s in srv.sched.active.items():
            assert s.generated[n[r]] == int(np.argmin(logits[r])) \
                != int(np.argmax(logits[r]))
        srv.step()
        assert (srv.device_steps, srv.steps_ahead) == (4, 3)
        with pytest.raises(HorovodTpuError, match="max_batch, vocab"):
            srv.last_logits = logits[:2]

    def test_an_empty_batch_lands_the_step_in_flight(self, kind):
        """A request's last token costs no decode step: the iteration
        that finds no row to step only fetches, and the first step of
        the next request finds nothing in flight."""
        cfg, params = _kind_model(kind)
        srv = InferenceServer(params, cfg, **self.KW)
        rng = np.random.RandomState(12)
        for i, n in enumerate([3, 4]):
            prompt = rng.randint(0, 64, size=5)
            srv.submit(prompt, n)
            grew = []
            while not srv.sched.drained():
                done = srv.step()
                seqs = list(srv.sched.active.values()) + done
                grew.append(sum(len(s.generated) for s in seqs))
            # a token a `server.step()`, the first in the admitting one
            assert grew == list(range(1, n + 1))
            assert srv.step_no == [3, 7][i]
            assert srv.device_steps == [2, 5][i]
            assert srv.steps_ahead == [1, 3][i]
            ref, _ = transformer_generate(
                params, cfg, jnp.asarray(prompt[None], jnp.int32), n)
            assert seqs[-1].generated == np.asarray(ref)[0].tolist()
        assert srv.rows_dropped == 0


@pytest.mark.parametrize("rounds", ["SSPPPP", "PPSSPP", "SPSPSP"])
def test_speculative_rounds_among_plain_steps(model, rounds):
    """A speculative round leaves its rows' ids decided on the host, a
    plain step the program's: in any order the chain is the greedy
    one."""
    cfg, params = model
    srv = InferenceServer(params, cfg, max_seq_tokens=40, max_batch=2,
                          page_tokens=4, gamma=3,
                          draft_params=transformer_init(
                              jax.random.PRNGKey(9), cfg), draft_cfg=cfg)
    rng = np.random.RandomState(11)
    reqs = {}
    for _ in range(3):
        prompt = rng.randint(0, 64, size=4)
        reqs[srv.submit(prompt, 14)] = prompt
    plain_steps = []
    real = srv._plain_step
    srv._plain_step = lambda rows, feed: (plain_steps.append(srv.step_no),
                                          real(rows, feed))
    got, ahead, was_plain = {}, 0, False
    for i in range(60):
        srv.force_spec = rounds[i % len(rounds)] == "S"
        for seq in srv.step():
            got[seq.req.req_id] = seq.generated
        # a plain step is dispatched ahead of the ids of the step before
        # exactly where that was a plain step too: a round lands it, and
        # so does an iteration that finds no row to step
        plain = plain_steps[-1:] == [i]
        ahead += plain and was_plain
        was_plain = plain
    assert srv.sched.drained()
    assert 0 < srv.spec_steps < srv.device_steps
    assert len(plain_steps) == srv.device_steps - srv.spec_steps
    assert srv.steps_ahead == ahead < srv.device_steps - srv.spec_steps
    assert (ahead > 0) == ("PP" in rounds)
    for rid, prompt in reqs.items():
        ref, _ = transformer_generate(
            params, cfg, jnp.asarray(prompt[None], jnp.int32), 14)
        assert got[rid] == np.asarray(ref)[0].tolist()


class TestBenchRecords:
    def test_append_and_stale_gate(self, tmp_path, caplog):
        path = str(tmp_path / "BENCH_serve.json")
        assert read_latest_record(path) is None
        append_record(path, {"bench": "decode_bench", "x": 1})
        rec = read_latest_record(path)
        assert rec["x"] == 1 and rec["stale"] is False
        assert "captured_utc" in rec
        # age a record past the gate
        old = {"bench": "decode_bench", "x": 2,
               "captured_unix": time.time() - 100 * 3600}
        with open(path, "a") as f:
            f.write(json.dumps(old) + "\n")
        with caplog.at_level("WARNING"):
            rec = read_latest_record(path)
        assert rec["stale"] is True and rec["stale_hours"] > 24
        assert any("stale" in m for m in caplog.messages)

    def test_run_trace_stats(self, model):
        cfg, params = model
        trace = make_trace(3, 5, cfg.vocab_size, prompt_lens=(3, 5),
                           max_new_lo=2, max_new_hi=6,
                           arrival_every=1.0)
        srv = InferenceServer(params, cfg, max_seq_tokens=16,
                              max_batch=2, page_tokens=4)
        stats = run_trace(srv, trace)
        assert stats["tokens_out"] == sum(mn for _, _, mn in trace)
        assert 0 < stats["batch_occupancy_mean"] <= 1
        assert 0 < stats["kv_pool_peak_utilization"] <= 1
        assert stats["request_p99_ms"] >= stats["request_p50_ms"]

    def test_make_trace_deterministic_and_bimodal(self):
        t1 = make_trace(5, 20, 64, long_frac=0.5, long_lo=90,
                        long_hi=99)
        t2 = make_trace(5, 20, 64, long_frac=0.5, long_lo=90,
                        long_hi=99)
        assert all((a[0] == b[0] and a[2] == b[2]
                    and np.array_equal(a[1], b[1]))
                   for a, b in zip(t1, t2))
        assert any(mn >= 90 for _, _, mn in t1)
        assert any(mn < 90 for _, _, mn in t1)


@pytest.mark.slow
class TestReplicaElastic:
    """np=2-style e2e: two serving replicas over the rendezvous
    control plane; the serve.replica_die fault kills one mid-stream;
    the manager's lease/respawn recovers with no lost sequence and
    token-identical results."""

    CONFIG = {
        "cfg": dict(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                    d_ff=64, n_layers=2, compute_dtype="float32"),
        "seed": 0,
        "serve": dict(max_seq_tokens=24, max_batch=2, page_tokens=4),
    }

    def _requests(self):
        rng = np.random.RandomState(1)
        return [(rng.randint(0, 64, size=4).tolist(),
                 int(rng.randint(2, 6))) for _ in range(6)]

    def _serve(self, child_env):
        from horovod_tpu.serve.replica import ReplicaManager
        env = {"JAX_PLATFORMS": "cpu"}
        env.update(child_env)
        with ReplicaManager(2, self.CONFIG, lease_ttl=10.0,
                            respawn_backoff=0.2,
                            child_env=env) as mgr:
            for prompt, mn in self._requests():
                mgr.submit(prompt, mn)
            results = mgr.wait_all(timeout=180)
            respawns = mgr._respawns
        return results, respawns

    def test_replica_death_recovers_all_sequences(self):
        baseline, r0 = self._serve({})
        assert r0 == 0
        assert len(baseline) == 6
        recovered, r1 = self._serve({
            "HOROVOD_FAULT_SPEC": "serve.replica_die@3:exit:1",
            "HOROVOD_FAULT_HOSTS": "replica1",
        })
        assert r1 >= 1                      # the dead replica respawned
        assert recovered == baseline        # no lost/garbled sequence
