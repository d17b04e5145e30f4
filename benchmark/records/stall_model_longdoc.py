"""What one stall of the host does to `tpot_p90_ms` in
`brumby14b_longdoc_steady`: `runners/lm_serve.py`'s loop replayed on a
clock of its own, with the two times the chip gave (my traced run, PR 28):
a decode iteration 36.54 ms whatever the rows (`decode_iter_ms.tpot`) and
a prefill 65.2 ms a thousand tokens (`prefill_ms_per_ktoken.tpot`).

    python benchmark/records/stall_model_longdoc.py > stall_model_longdoc.json

It is arithmetic on the traffic file's own plan, no measurement: it says
which requests meet which prefills, and so how the percentile moves when
the loop stands still once for 0.2 to 2 s somewhere in the window.  Kept
because it gives the undisturbed cell closely (`tpot_p90_ms` 70.1 for
69.6 to 71.0 measured, `ttft_p90_ms` 1102 for 1071, rows active 68.0% for
68.3%), and so is what says why three of 18 runs read 76 to 118.
"""
import collections
import copy
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import traffic as traffic_mod  # noqa: E402

DECODE_S, PREFILL_S_PER_TOKEN, WINDOW_S = 0.03654, 65.2e-6, 40.0


def run(tr, stall=None, jitter=0.0, rng=None):
    """One window.  `stall` (at_s, for_s): the loop stands still once.
    `jitter`: each step's time varies by that share (normal)."""
    plan, sv, ramp = traffic_mod.plan(tr), tr["server"], tr["ramp"]
    rows, group = sv["max_batch"], ramp["max_group"]
    pending, queue, active = collections.deque(), collections.deque(), {}
    times, t, t0, stalled = {}, 0.0, None, False
    vary = (lambda: 1.0 + jitter * rng.standard_normal()) if jitter \
        else (lambda: 1.0)

    def step():
        nonlocal t, stalled
        while pending and len(queue) < group:
            queue.append(pending.popleft())
        while queue and len(active) < rows:          # admit: prefill whole
            r = queue.popleft()
            t += PREFILL_S_PER_TOKEN * r.prompt_len * vary()
            active[id(r)] = r
            times[id(r)] = []
        ended = []
        for k, r in list(active.items()):            # sample
            times[k].append(None)
            if len(times[k]) >= r.output_len:
                ended.append(k)
                del active[k]
        t += DECODE_S * vary() if active else 0.0005
        if stall and t0 is not None and not stalled \
                and t - t0 >= stall[0]:
            t, stalled = t + stall[1], True
        for k in list(active) + ended:
            times[k] = [t if x is None else x for x in times[k]]

    def drain():
        while pending or queue or active:
            step()

    prompt, out = plan.warm_pair
    warm = lambda o: traffic_mod.PlannedRequest(-10 ** 6, prompt, o, -1.0)
    for n in range(1, group + 1):
        pending.extend([warm(out)] * n)
        drain()
    pending.extend(warm(out + rows // group + 2 + i) for i in range(rows))
    drain()
    for r in plan.ramp:
        pending.append(r)
        for _ in range(ramp["stagger_steps"]):
            step()
    for _ in range(ramp["settle_steps"]):
        step()
    t0, nxt, due, reqs = t, 0, [], plan.requests
    steps = held = 0
    while True:
        now = t - t0
        if now >= WINDOW_S and all(
                len(times.get(id(r), ())) >= r.output_len for r in due):
            break
        while nxt < len(reqs) and reqs[nxt].due_s <= now:
            pending.append(reqs[nxt])
            if reqs[nxt].due_s < WINDOW_S:
                due.append(reqs[nxt])
            nxt += 1
        if not (active or queue or pending):
            t = t0 + reqs[nxt].due_s
            continue
        step()
        if t - t0 < WINDOW_S:
            steps, held = steps + 1, held + len(active)
    tpot = [1e3 * (times[id(r)][-1] - times[id(r)][0]) / (r.output_len - 1)
            for r in due]
    ttft = [1e3 * (times[id(r)][0] - t0 - r.due_s) for r in due]
    return {"requests_due": len(due),
            "tpot_p90_ms": float(np.percentile(tpot, 90)),
            "ttft_p90_ms": float(np.percentile(ttft, 90)),
            "rows_active_share": held / steps / rows}


def main():
    here = os.path.join(ROOT, "benchmark", "traffic", "longdoc_steady.json")
    with open(here) as f:
        base = json.load(f)
    out = {"undisturbed": run(base), "one_stall": [], "other_traffic": []}
    quiet = [run(base, jitter=0.01, rng=np.random.default_rng(i))
             ["tpot_p90_ms"] for i in range(40)]
    out["steps_vary_1_percent"] = {"runs": 40, "lowest": min(quiet),
                                   "highest": max(quiet)}
    median = float(np.median(quiet))
    for for_s in (0.2, 0.5, 1.0, 2.0, 5.0):
        got = []
        for i in range(80):
            rng = np.random.default_rng(i)
            got.append(run(base, (rng.uniform(0, WINDOW_S), for_s), 0.005,
                           rng)["tpot_p90_ms"])
        out["one_stall"].append({
            "stall_s": for_s, "runs": 80, "median": float(np.median(got)),
            "highest": max(got),
            "share_over_3_percent": float(np.mean(
                np.asarray(got) > 1.03 * median))})
    # What the review proposed, and other draws of the same mix.
    short = copy.deepcopy(base)
    short["pairs"] = [dict(p, weight=3 if (p["prompt"], p["output"])
                           == (8192, 128) else p["weight"])
                      for p in base["pairs"] if p["prompt"] != 12288]
    tries = [("no 12288-token pairs at 1.4/s", short, 1.4, base["shape_seed"]),
             ("no 12288-token pairs at 1.5/s", short, 1.5, base["shape_seed"])]
    tries += [(f"shape_seed {s}", base, 1.3, s) for s in (28004, 28011, 28013)]
    for name, tr, rate, seed in tries:
        tr = copy.deepcopy(tr)
        tr["arrivals"]["rate_per_s"], tr["shape_seed"] = rate, seed
        quiet = [run(tr, jitter=0.01, rng=np.random.default_rng(i))
                 ["tpot_p90_ms"] for i in range(40)]
        hit = []
        for i in range(60):
            rng = np.random.default_rng(i)
            hit.append(run(tr, (rng.uniform(0, WINDOW_S), 1.0), 0.005, rng)
                       ["tpot_p90_ms"])
        out["other_traffic"].append({
            "traffic": name, "requests_due": run(tr)["requests_due"],
            "quiet_range_share": (max(quiet) - min(quiet))
            / float(np.median(quiet)),
            "share_over_3_percent_after_1s_stall": float(np.mean(
                np.asarray(hit) > 1.03 * float(np.median(quiet))))})
    json.dump(out, sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
