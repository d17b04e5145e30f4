"""The one general traffic generator.

A traffic mix is a data file, `benchmark/traffic/<name>.json`; this reads
any of them.  What a run does (lengths, order, due times) comes from the
file's own `shape_seed` and is the same in every run of the cell;
`--seed` only chooses the token ids.  A later PR adds a mix by adding a
file, never by touching this one.

Request mixes (`"kind": "requests"`):

  pairs        [{"prompt": n, "output": n, "weight": w}, ...] — the grid.
               Every (prompt, pages) pair is a compiled prefill program,
               so lengths come from a short list, not a continuum.
  order        requests come in shuffled blocks; a block holds each pair
               `weight` times, so any window sees the grid's mix.
  arrivals     {"process": "backlog", "requests": n}: all queued before
               the window.  {"process": "open", "rate_per_s": r,
               "horizon_s": h}: Poisson arrivals (exponential gaps with
               mean 1/r), due times on the wall clock from the window's
               opening, enough of them to outlast `horizon_s`.
  ramp         how the batch is filled before the window, counted in
               server steps and never on the clock, so that a run that
               compiles opens its window in the same state as one that
               does not: 1..`max_group` copies of `warm_pair` admitted
               in one step and a staircase of max_batch rows that end
               one a step (the pool has one program per number of rows
               admitted and per number active), then
               `requests` requests one every `stagger_steps` steps (every
               distinct pair once, longest outputs first, so that they
               are at mixed stages when the window opens), then
               `settle_steps` more steps.  The harness never hands the
               server more than `max_group` requests between two steps
               nor lets its queue hold more: the rest wait, first in
               first out, with the load generator, their clocks running.

Prompts are independent: nothing is shared between two requests.  A mix
that needs another arrival law or shared prefixes brings the parameter
with its traffic file and cell (PERF.md, Open questions).

Batch mixes (`"kind": "batches"`) carry sizes only and are read by the
training runners directly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class PlannedRequest:
    index: int
    prompt_len: int
    output_len: int
    due_s: float            # from the window's opening; < 0: ramp/backlog


@dataclasses.dataclass(frozen=True)
class Plan:
    ramp: List[PlannedRequest]       # served before the window opens
    requests: List[PlannedRequest]   # the measured traffic
    pairs: List[tuple]               # distinct (prompt, output)
    warm_pair: tuple                 # (prompt, output) of the warm rounds


def _block(pairs: List[Dict]) -> List[tuple]:
    out = []
    for p in pairs:
        out += [(int(p["prompt"]), int(p["output"]))] * int(p["weight"])
    return out


def _lengths(pairs: List[Dict], n: int, rng) -> List[tuple]:
    block, out = _block(pairs), []
    while len(out) < n:
        out += [block[i] for i in rng.permutation(len(block))]
    return out[:n]


def plan(traffic: Dict) -> Plan:
    if traffic["kind"] != "requests":
        raise ValueError(f"traffic kind {traffic['kind']!r} has no requests")
    rng = np.random.default_rng(int(traffic["shape_seed"]))
    pairs = traffic["pairs"]
    arr = traffic["arrivals"]
    distinct = sorted({(int(p["prompt"]), int(p["output"])) for p in pairs})

    # The ramp: every distinct pair once (longest outputs first, so that
    # they are still running, at mixed stages, when the window opens),
    # then shuffled blocks up to the count asked for.
    ramp_cfg = traffic["ramp"]
    ramp_lens = sorted(distinct, key=lambda p: -p[1])
    extra = int(ramp_cfg["requests"]) - len(ramp_lens)
    if extra > 0:
        ramp_lens += _lengths(pairs, extra, rng)
    ramp = [PlannedRequest(-1 - i, p, o, -1.0)
            for i, (p, o) in enumerate(ramp_lens)]

    if arr["process"] == "backlog":
        n = int(arr["requests"])
        due = np.full(n, -1.0)
    elif arr["process"] == "open":
        rate = float(arr["rate_per_s"])
        n = int(rate * float(arr["horizon_s"])) + 1
        due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    else:
        raise ValueError(f"arrival process {arr['process']!r}")
    lens = _lengths(pairs, n, rng)
    reqs = [PlannedRequest(i, lens[i][0], lens[i][1], float(due[i]))
            for i in range(n)]
    warm = ramp_cfg["warm_pair"]
    return Plan(ramp, reqs, distinct,
                (int(warm["prompt"]), int(warm["output"])))


def prompt_tokens(seed: int, req: PlannedRequest, vocab: int) -> np.ndarray:
    """The token ids of one request: from `--seed` and the request's
    index."""
    own = np.random.default_rng([int(seed), 2, req.index & 0xFFFFFFFF])
    return own.integers(0, vocab, size=req.prompt_len, dtype=np.int32)
