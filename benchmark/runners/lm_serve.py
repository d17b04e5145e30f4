"""A decoder LM served through `InferenceServer` (serve/server.py): paged
cache, continuous batching, greedy tokens, bf16 weights.

One thread drives everything: between two server steps the load
generator hands over the requests that are due, then `server.step()`
runs (admission, whole-prompt prefill, one decode step), then the harness
looks at which rows grew a token.  A token's time is the end of the step
that produced it, on the host's clock; a request's clock starts when it
was due, not when it was sent.  That clock leaves out the seconds a
traced run spends starting and stopping the profiler (`window`).
"""
from __future__ import annotations

import collections
import gc
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import traffic as traffic_mod
from benchmark.lib import weights
from benchmark.lib.harness import Check, WindowResult
from benchmark.lib.stats import pct
from benchmark.reference import serve_check

DRAIN_LIMIT_S = 40.0     # after the close, for requests due inside it


class Tracked:
    """One request as the client sees it."""
    __slots__ = ("plan", "prompt", "sent", "token_times", "seq", "admitted",
                 "failed")

    def __init__(self, plan, prompt):
        self.plan, self.prompt = plan, prompt
        self.sent = None
        self.token_times: List[float] = []
        self.seq = None
        self.admitted = None
        self.failed = False


class Runner:
    def __init__(self, ctx):
        from horovod_tpu.models import TransformerConfig
        from horovod_tpu.serve import InferenceServer

        self.ctx = ctx
        m, tr = ctx.config, ctx.traffic
        self.m = m
        self.key = weights.seed_key(ctx.seed)
        self.plan = traffic_mod.plan(tr)
        tcfg = TransformerConfig(
            vocab_size=m["vocab_size"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"], d_head=m["head_dim"],
            d_ff=m["intermediate_size"], n_layers=m["num_hidden_layers"],
            n_kv_heads=m["num_key_value_heads"],
            attn_window=m.get("sliding_window") or 0,
            rope_theta=m["rope_theta"], compute_dtype=jnp.bfloat16)
        params = jax.jit(lambda k: weights.lm_params(k, m, jnp.bfloat16))(
            self.key)
        sv = tr["server"]
        self.server = InferenceServer(
            params, tcfg, max_seq_tokens=sv["max_seq_tokens"],
            max_batch=sv["max_batch"], page_tokens=m["serve"]["page_tokens"],
            pool_pages=sv.get("pool_pages"))
        del params
        self.by_id: Dict[int, Tracked] = {}
        self.finished: List[Tracked] = []
        self.ended = set()               # plan indices finished or refused
        self.prefill_tokens = 0
        self.ran_out = False
        # Due requests wait here, first in first out; the server's own
        # queue is only a staging area of at most `max_group`, so that no
        # step admits more rows than the warm rounds compiled for.
        self.pending = collections.deque()
        self.max_group = int(tr["ramp"]["max_group"])
        self._ramp()

    # -- driving the server --------------------------------------------

    def _submit(self, plan_req, now: float) -> Tracked:
        prompt = traffic_mod.prompt_tokens(
            self.ctx.seed, plan_req, self.m["vocab_size"])
        t = Tracked(plan_req, prompt)
        t.sent = now
        try:
            rid = self.server.submit(prompt, plan_req.output_len)
        except Exception as e:                    # refused: counts as failed
            print(f"request {plan_req.index} refused: {e!r}")
            t.failed = True
            self.finished.append(t)
            self.ended.add(plan_req.index)
            return t
        self.by_id[rid] = t
        return t

    def _feed(self, clock) -> None:
        """Hand the server what is due, oldest first, `max_group` at most
        between two steps and never more than that in its queue."""
        srv = self.server
        n = self.max_group - srv.sched.queue_depth()
        while self.pending and n > 0:
            self._submit(self.pending.popleft(), clock())
            n -= 1

    def _step(self, clock) -> None:
        """Hand over what is due, run one server step, then note every
        token it produced."""
        srv = self.server
        with self.ctx.span("loadgen"):
            self._feed(clock)
        with self.ctx.span("server.step"):
            done = srv.step()
        now = clock()
        for seq in list(srv.sched.active.values()) + done:
            t = self.by_id[seq.req.req_id]
            if t.seq is None:
                t.seq, t.admitted = seq, now
                self.prefill_tokens += len(t.prompt)
            new = len(seq.generated) - len(t.token_times)
            if new:
                t.token_times.extend([now] * new)
        for seq in done:
            t = self.by_id[seq.req.req_id]
            self.finished.append(t)
            self.ended.add(t.plan.index)

    def _drain(self, clock) -> None:
        while self.pending or not self.server.sched.drained():
            self._step(clock)

    def _ramp(self) -> None:
        """Warm every program, then fill the batch; counted in server
        steps, never on the clock (see lib/traffic.py)."""
        ramp, srv = self.ctx.traffic["ramp"], self.server
        clock = time.perf_counter
        prompt, output = self.plan.warm_pair
        warm = lambda out: traffic_mod.PlannedRequest(
            -10 ** 6, prompt, out, -1.0)
        # 1..max_group rows admitted in one step: the pool refreshes its
        # view with one program per number of rows admitted.
        for n in range(1, self.max_group + 1):
            self.pending.extend([warm(output)] * n)
            self._drain(clock)
        # A staircase: max_batch rows that end one a step, so that every
        # number of active rows (one write-back program each) occurs.
        base = output + srv.max_batch // self.max_group + 2
        self.pending.extend(warm(base + i) for i in range(srv.max_batch))
        self._drain(clock)
        # The traffic's own first requests, one every few steps.
        for req in self.plan.ramp:
            self.pending.append(req)
            for _ in range(ramp["stagger_steps"]):
                self._step(clock)
        if self.ctx.traffic["arrivals"]["process"] == "backlog":
            self.pending.extend(self.plan.requests)
        for _ in range(ramp["settle_steps"]):
            self._step(clock)

    # -- the measured window -------------------------------------------

    def window(self, seconds: float) -> WindowResult:
        ctx, srv = self.ctx, self.server
        open_loop = ctx.traffic["arrivals"]["process"] == "open"
        reqs = self.plan.requests if open_loop else []
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0

        def off_the_clock(held: float) -> None:
            # The profiler's start and stop hold this thread for seconds,
            # between two server steps.  The clock stands still
            # meanwhile: nothing becomes due, nothing is late, no token's
            # time falls in the hole and the drain limit does not run, so
            # a traced run offers the traffic an untraced run does.
            nonlocal t0
            t0 += held

        tokens0 = sum(len(t.token_times) for t in self.by_id.values())
        prefill0, steps0 = self.prefill_tokens, srv.device_steps
        occ0 = srv.occupancy_sum
        finished0 = len(self.finished)
        nxt, measured_idx, closed, counters = 0, set(), None, {}
        timeline = []

        def close(now: float) -> float:
            # The window closes at a step boundary.  Counters stop here;
            # the load stays on until what was due inside has ended.
            if ctx.tracer:
                off_the_clock(ctx.tracer.stop())
            tokens1 = sum(len(t.token_times) for t in self.by_id.values())
            counters.update(
                device_steps=srv.device_steps - steps0,
                occupancy_sum=srv.occupancy_sum - occ0,
                output_tokens=tokens1 - tokens0,
                prefill_tokens=self.prefill_tokens - prefill0,
                finished_requests=len(self.finished) - finished0)
            return now

        while True:
            now = clock()
            if closed is None:
                if ctx.tracer:
                    off_the_clock(ctx.tracer.poll(now))
                if now >= seconds:
                    closed = close(now)
            if closed is not None and (
                    not open_loop or now >= closed + DRAIN_LIMIT_S
                    or measured_idx <= self.ended):
                break
            while nxt < len(reqs) and reqs[nxt].due_s <= now:
                self.pending.append(reqs[nxt])
                if reqs[nxt].due_s < seconds:
                    measured_idx.add(reqs[nxt].index)
                nxt += 1
            if srv.sched.drained() and not self.pending:
                if nxt >= len(reqs):       # the traffic ran out: too short
                    self.ran_out = closed is None
                    closed = close(clock()) if closed is None else closed
                    break
                time.sleep(max(0.0, min(0.0005, reqs[nxt].due_s - clock())))
                continue
            self._step(clock)
            if ctx.timeline_path:
                timeline.append((clock(), len(srv.sched.active),
                                 srv.sched.queue_depth()
                                 + len(self.pending)))

        window_s = closed
        e2e, samples = {}, {}
        e2e["serve_tokens_per_s"] = (counters["output_tokens"]
                                     + counters["prefill_tokens"]) / window_s
        failed = 0
        if open_loop:
            tpot, ttft, late = [], [], []
            by_index = {t.plan.index: t for t in self.finished}
            for i in sorted(measured_idx):
                t = by_index.get(i)
                if t is None or t.failed:    # never ended, or refused
                    failed += 1
                    continue
                n = len(t.token_times)
                late.append(1e3 * (t.sent - t.plan.due_s))
                ttft.append(1e3 * (t.token_times[0] - t.plan.due_s))
                tpot.append(1e3 * (t.token_times[-1] - t.token_times[0])
                            / (n - 1))
            worst = [float("inf")] * failed     # a failure is the worst
            e2e["tpot_p90_ms"] = pct(tpot + worst, 90)
            samples = {"late_ms": late, "ttft_ms": ttft + worst,
                       "tpot_ms": tpot}
            attempted = len(measured_idx)
            print(f"window: {attempted} requests due in {window_s:.2f} s, "
                  f"{failed} failed; tpot p50 {pct(tpot, 50):.3f} p90 "
                  f"{pct(tpot, 90):.3f} ms; ttft p50 {pct(ttft, 50):.2f} "
                  f"p90 {pct(ttft, 90):.2f} ms; late p99 "
                  f"{pct(late, 99):.2f} ms; waiting now "
                  f"{srv.sched.queue_depth() + len(self.pending)}")
        else:
            attempted = counters["finished_requests"]
        print(f"window: {counters['device_steps']} steps, "
              f"{counters['output_tokens']} output and "
              f"{counters['prefill_tokens']} prompt tokens in "
              f"{window_s:.3f} s; occupancy "
              f"{100 * counters['occupancy_sum'] / max(1, counters['device_steps']):.2f} %")
        if ctx.tracer:
            print(f"profiler: start {ctx.tracer.start_s:.3f} s, stop "
                  f"{ctx.tracer.stop_s:.3f} s off the clock")
        if ctx.timeline_path:
            self._write_timeline(timeline, window_s)
        return WindowResult(attempted=attempted, failed=failed,
                            end_to_end=e2e, counters=counters,
                            samples=samples)

    def _write_timeline(self, steps, window_s: float) -> None:
        """Per second of the window: tokens emitted, prompt tokens
        prefilled, requests completed, mean rows active and queue depth;
        then each measured request's times."""
        n = int(window_s) + 1
        out = [dict(second=i, output_tokens=0, prompt_tokens=0, completed=0)
               for i in range(n)]
        for t in self.by_id.values():
            for x in t.token_times:
                if 0 <= x < n:
                    out[int(x)]["output_tokens"] += 1
            if t.admitted is not None and 0 <= t.admitted < n:
                out[int(t.admitted)]["prompt_tokens"] += len(t.prompt)
            if t.token_times and len(t.token_times) >= t.plan.output_len \
                    and 0 <= t.token_times[-1] < n:
                out[int(t.token_times[-1])]["completed"] += 1
        reqs = [dict(index=t.plan.index, due=t.plan.due_s, sent=t.sent,
                     prompt=t.plan.prompt_len, output=t.plan.output_len,
                     first=t.token_times[0], last=t.token_times[-1])
                for t in self.by_id.values()
                if t.plan.index >= 0 and t.token_times]
        with open(self.ctx.timeline_path, "w") as f:
            json.dump({"workload": self.ctx.cell["name"],
                       "seconds": out, "requests": reqs,
                       "steps": [[round(a, 4), b, c] for a, b, c in steps]},
                      f)

    # -- correct --------------------------------------------------------

    def sample(self) -> List[Dict]:
        """Finished requests of the traffic (not of the warm rounds),
        drawn from the seed, the longest among them."""
        done = [t for t in self.finished
                if not t.failed and t.plan.index > -10 ** 6
                and len(t.token_times) >= t.plan.output_len]
        n = min(self.m["serve"]["check_requests"], len(done))
        if n == 0:
            return []
        longest = max(done, key=lambda t: (len(t.prompt) + t.plan.output_len,
                                           -t.plan.index))
        rest = [t for t in done if t is not longest]
        rng = np.random.default_rng([int(self.ctx.seed), 7])
        picked = [longest] + [rest[i] for i in
                              rng.permutation(len(rest))[:n - 1]]
        return [{"prompt": t.prompt, "served": list(t.seq.generated)}
                for t in picked]

    def free_program(self) -> None:
        self.server = None
        gc.collect()     # the server's callbacks hold it in a cycle

    def readings(self, control: str = "") -> Dict:
        sample = self.sample()
        wrong = sum(1 for t in self.finished
                    if not t.failed
                    and len(t.seq.generated) != t.plan.output_len)
        self.free_program()
        n_out = max(o for _, o in self.plan.pairs + [self.plan.warm_pair])
        want = self.m["serve"]["check_requests"]
        limit = self.m["limits"]["logit_gap"]
        tokens = sum(len(s["served"]) for s in sample)
        what = (f"widest gap of a served token's logit below the "
                f"reference's best ({tokens} tokens of {len(sample)} "
                f"requests)")
        gap = serve_check.widest_gap(self.key, self.m, sample, n_out) \
            if sample else float("nan")
        out = {"program": [
            Check("the traffic ran out before the window closed",
                  int(self.ran_out), 0),
            Check("finished requests with a wrong token count", wrong, 0),
            Check("requests compared short of the sample asked",
                  want - len(sample), 0),
            Check(what, gap, limit)]}
        if control:
            out["control"] = [Check(what, serve_check.widest_gap(
                self.key, self.m, sample, n_out, control), limit)]
        return out

    def check(self) -> List[Check]:
        return self.readings()["program"]
