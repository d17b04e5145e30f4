"""A retention LM (`family: retention_lm`) served through `InferenceServer`
(serve/server.py): one fixed state a row in the decode view, continuous
batching, greedy tokens, bf16 weights.

The driving of the server, the window, the sampling of finished requests
and the timeline are `lm_serve.Runner`'s, unchanged: the same `submit` /
`step` loop serves every model.  What differs is what is built (the
configuration's retention keys, the weight tree with the new leaves) and
the plain reference the served tokens are held against.
"""
from __future__ import annotations

import collections
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import traffic as traffic_mod
from benchmark.lib import weights, weights_retention
from benchmark.lib.harness import Check
from benchmark.reference import retention_check
from benchmark.runners import lm_serve


def transformer_config(m: Dict, state_dtype=None):
    """The program's configuration for file `m`.  Raises on a program
    that has no retention layer, or that runs another power or chunk
    length than the file states, before anything is built."""
    from horovod_tpu.models import TransformerConfig, decode
    sv = m["serve"]
    ran = {"chunk_tokens": (sv["chunk_tokens"], decode.RETENTION_CHUNK),
           "retention_power": (m["assumed_sizes"]["retention_power"], 2),
           "normaliser_eps": (m["assumed_sizes"]["normaliser_eps"],
                              decode.RETENTION_EPS)}
    for name, (stated, program) in ran.items():
        if stated != program:
            raise ValueError(f"{m['name']} states {name} {stated}, the "
                             f"program runs {program}")
    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], d_head=m["head_dim"],
        d_ff=m["intermediate_size"], n_layers=m["num_hidden_layers"],
        n_kv_heads=m["num_key_value_heads"], rope_theta=m["rope_theta"],
        compute_dtype=jnp.bfloat16, attn_kind="retention",
        state_dtype=jnp.dtype(state_dtype or sv["state_dtype"]))


def served_again(server, prompt, n_out: int):
    """Serve `prompt` once more through `server` and return the tokens it
    emits and, for each but the first, the logits that chose it: the
    request's row of `server.last_logits` between two steps, which is
    what the decode step just read out of the row's state.  (The first
    token is chosen inside the admitting step, from the prefill's
    logits.)  Whatever else the server still holds goes on beside it."""
    rid = server.submit(prompt, n_out)
    logits = []
    while True:
        done = server.step()
        seq = next((s for s in list(server.sched.active.values()) + done
                    if s.req.req_id == rid), None)
        if seq is None:                       # still queued
            continue
        if seq.done:
            return list(seq.generated), np.stack(logits)
        logits.append(server.last_logits[seq.row].copy())


class Runner(lm_serve.Runner):
    def __init__(self, ctx):
        from horovod_tpu.serve import InferenceServer

        self.ctx = ctx
        m, tr = ctx.config, ctx.traffic
        self.m = m
        tcfg = transformer_config(m)
        self.key = weights.seed_key(ctx.seed)
        self.plan = traffic_mod.plan(tr)
        params = jax.jit(
            lambda k: weights_retention.params(k, m, jnp.bfloat16))(self.key)
        sv = tr["server"]
        self.server = InferenceServer(
            params, tcfg, max_seq_tokens=sv["max_seq_tokens"],
            max_batch=sv["max_batch"])
        del params
        self.by_id = {}
        self.finished = []
        self.ended = set()
        self.prefill_tokens = 0
        self.ran_out = False
        self.pending = collections.deque()
        self.max_group = int(tr["ramp"]["max_group"])
        self._ramp()

    def readings(self, control: str = "") -> Dict:
        """`control` "fp8": the reference in float8 in the program's
        place, for the widest gap.  "state_bf16": the program with its
        state held in bfloat16 where the configuration states float32,
        for the state's error."""
        from horovod_tpu.serve import InferenceServer

        sample = self.sample()
        wrong = sum(1 for t in self.finished
                    if not t.failed
                    and len(t.seq.generated) != t.plan.output_len)
        n_out = max(o for _, o in self.plan.pairs + [self.plan.warm_pair])
        again = {}
        if sample:
            # The longest prompt sampled (`sample` puts it first), for as
            # many tokens as the traffic's longest answer has: what a
            # state loses, it loses update by update.
            prompt = sample[0]["prompt"]
            n = min(n_out, self.ctx.traffic["server"]["max_seq_tokens"]
                    - len(prompt))
            again["program"] = served_again(self.server, prompt, n)
            if control == "state_bf16":
                params, sv = self.server.params, self.ctx.traffic["server"]
                self.free_program()
                half = InferenceServer(
                    params, transformer_config(self.m, "bfloat16"),
                    max_seq_tokens=sv["max_seq_tokens"],
                    max_batch=sv["max_batch"])
                again["control"] = served_again(half, prompt, n)
                del params, half
        self.free_program()
        want = self.m["serve"]["check_requests"]
        limits = self.m["limits"]
        tokens = sum(len(s["served"]) for s in sample)
        gap_what = (f"widest gap of a served token's logit below the "
                    f"reference's best ({tokens} tokens of {len(sample)} "
                    f"requests)")
        gap = retention_check.widest_gap(self.key, self.m, sample, n_out) \
            if sample else float("nan")

        def state(side: str) -> Check:
            if side not in again:
                return Check("relative error of the logits read out of "
                             "the state", float("nan"),
                             limits["state_error"])
            toks, logits = again[side]
            return Check(
                f"relative error of the logits read out of the state "
                f"(the later half of {len(logits)} decode steps behind "
                f"{len(prompt)} tokens, served again)",
                retention_check.state_error(self.key, self.m, prompt, toks,
                                            logits, n_out),
                limits["state_error"])

        out = {"program": [
            Check("the traffic ran out before the window closed",
                  int(self.ran_out), 0),
            Check("finished requests with a wrong token count", wrong, 0),
            Check("requests compared short of the sample asked",
                  want - len(sample), 0),
            Check(gap_what, gap, limits["logit_gap"]),
            state("program")]}
        if control == "state_bf16":
            out["control"] = [state("control")]
        elif control:
            out["control"] = [Check(gap_what, retention_check.widest_gap(
                self.key, self.m, sample, n_out, control),
                limits["logit_gap"])]
        return out
