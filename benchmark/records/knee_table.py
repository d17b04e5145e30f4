"""From the timelines of a rate sweep (`run.py --timeline`, one file per
rate) to the knee table kept in this directory.

    python benchmark/records/knee_table.py <seconds> <rate>=<file> ... > knee_sweep.json

A rate is sustained when at least 90% of the requests due inside the
window got their first token within 250 ms of being due and their later
tokens at no more than 100 ms apiece (DistServe's chatbot limits,
arXiv:2401.09670), and the backlog did not grow: the queue over the
window's last fifth is no deeper than over its first fifth plus one.
The knee is the highest sustained rate below the first that is not.
"""
import json
import statistics
import sys

TTFT_MS, TPOT_MS, SHARE = 250.0, 100.0, 0.90


def row(rate: float, path: str, seconds: float) -> dict:
    with open(path) as f:
        d = json.load(f)
    reqs = [r for r in d["requests"] if 0 <= r["due"] < seconds]
    ttft = [1e3 * (r["first"] - r["due"]) for r in reqs]
    tpot = [1e3 * (r["last"] - r["first"]) / (r["output"] - 1) for r in reqs]
    ok = sum(a <= TTFT_MS and b <= TPOT_MS for a, b in zip(ttft, tpot))
    steps = [s for s in d["steps"] if s[0] < seconds]
    fifth = max(1, len(steps) // 5)
    q_first = statistics.mean(s[2] for s in steps[:fifth])
    q_last = statistics.mean(s[2] for s in steps[-fifth:])
    qs = lambda xs, q: sorted(xs)[min(len(xs) - 1, int(q * len(xs)))]
    attained = ok / len(reqs)
    return {"rate_per_s": rate, "requests_due": len(reqs),
            "attained_share": attained,
            "ttft_p50_ms": qs(ttft, 0.5), "ttft_p90_ms": qs(ttft, 0.9),
            "tpot_p50_ms": qs(tpot, 0.5), "tpot_p90_ms": qs(tpot, 0.9),
            "rows_active_mean": statistics.mean(s[1] for s in steps),
            "queue_first_fifth": q_first, "queue_last_fifth": q_last,
            "sustained": bool(attained >= SHARE and q_last <= q_first + 1)}


def main(argv):
    seconds = float(argv[0])
    rows = []
    for arg in argv[1:]:
        rate, path = arg.split("=", 1)
        rows.append(row(float(rate), path, seconds))
    rows.sort(key=lambda r: r["rate_per_s"])
    knee = None
    for r in rows:
        if not r["sustained"]:
            break
        knee = r["rate_per_s"]
    json.dump({"limits": {"ttft_ms": TTFT_MS, "tpot_ms": TPOT_MS,
                          "share": SHARE},
               "window_s": seconds, "rows": rows, "knee_rate_per_s": knee},
              sys.stdout, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
