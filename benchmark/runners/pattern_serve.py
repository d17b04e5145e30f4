"""A patterned decoder with routed experts (`family: pattern_moe_lm`)
served through `InferenceServer` (serve/server.py): full-attention layers
in pages, sliding layers in one ring of `sliding_window` slots a row,
top-k routed experts through one grouped product, continuous batching,
greedy tokens, bf16 weights.

The driving of the server, the window, the sampling of finished requests
and the timeline are `lm_serve.Runner`'s, unchanged.  What differs is
what is built (the configuration's pattern, the weight tree stacked by
kind of layer), three sums a step that the window's counters do not
have (what the expert layers counted), cut at the window's
`device_steps`, and the plain reference
the served tokens are held against, by the MEAN of the gap that the
other serving runners take the widest of: routing is discrete
(`reference/pattern_check.py` has why).
"""
from __future__ import annotations

import collections
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import traffic as traffic_mod
from benchmark.lib import weights, weights_pattern
from benchmark.lib.harness import Check
from benchmark.reference import pattern_check
from benchmark.runners import lm_serve

#: the server's running sums a step is noted with (0 where a program
#: has no such counter)
SERVER_SUMS = ("experts_hit_sum", "expert_load_max_sum", "moe_layer_steps")
#: the printed table of `readings`: the widest gap with the positions
#: under each routing margin left out (reference/pattern_check.py)
NEAR_TIE_MARGINS = (0.01, 0.02, 0.04, 0.06)


def transformer_config(m: Dict, dtype=jnp.bfloat16, held=None):
    """The program's configuration for file `m`: the pattern by layer,
    heads, window and rotary form by kind of attention, the experts."""
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.transformer import AttnSpec, Rotary

    n = m["num_hidden_layers"]
    types = list(m["layer_types"][:n])
    heads = m["num_attention_heads_per_layer"]
    specs = []
    for t in dict.fromkeys(types):
        hs = {heads[l] for l in range(n) if types[l] == t}
        if len(hs) != 1:
            raise ValueError(f"{t} layers differ in heads: {sorted(hs)}")
        rp = m["rope_parameters"][t]
        yarn = rp.get("rope_type", "default") == "yarn"
        rotary = Rotary(
            theta=float(rp["rope_theta"]),
            share=float(rp.get("partial_rotary_factor", 1.0)),
            yarn_factor=float(rp["factor"]) if yarn else 0.0,
            yarn_original=rp["original_max_position_embeddings"]
            if yarn else 0,
            yarn_beta_fast=float(rp["beta_fast"]) if yarn else 32.0,
            yarn_beta_slow=float(rp["beta_slow"]) if yarn else 1.0,
            attention_factor=float(rp["attention_factor"]) if yarn else 1.0)
        specs.append((t, AttnSpec(
            n_heads=hs.pop(), rotary=rotary,
            window=m["sliding_window"] if t == "sliding_attention" else 0)))
    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=max(heads[:n]), d_head=m["head_dim"],
        d_ff=m["intermediate_size"], n_layers=n,
        n_kv_heads=m["num_key_value_heads"], compute_dtype=dtype,
        layer_attn=tuple(types),
        layer_mlp=tuple("dense" if k == "dense" else "experts"
                        for k in m["mlp_layer_types"][:n]),
        attn_specs=tuple(specs), attn_gate=bool(m["gating"]),
        n_experts=m["num_experts"],
        experts_per_token=m["num_experts_per_tok"],
        expert_ff=m["moe_intermediate_size"],
        shared_ff=m["shared_expert_intermediate_size"],
        routed_scale=float(m["moe_routed_scaling_factor"]),
        experts_held=held)


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
            lambda k: weights_pattern.params(k, m, jnp.bfloat16))(self.key)
        sv = tr["server"]
        self.server = InferenceServer(
            params, tcfg, max_seq_tokens=sv["max_seq_tokens"],
            max_batch=sv["max_batch"], page_tokens=m["serve"]["page_tokens"],
            pool_pages=sv.get("pool_pages"))
        del params
        self.by_id = {}
        self.finished = []
        self.ended = set()
        self.prefill_tokens = 0
        self.ran_out = False
        self.pending = collections.deque()
        self.max_group = int(tr["ramp"]["max_group"])
        #: device_steps -> the sums as they stood after that step
        self.sums_at: Dict[int, tuple] = {}
        self._ramp()

    def _sums(self) -> tuple:
        return tuple(getattr(self.server, n, 0) for n in SERVER_SUMS)

    def _step(self, clock) -> None:
        super()._step(clock)
        self.sums_at[self.server.device_steps] = self._sums()

    def window(self, seconds: float):
        steps0 = self.server.device_steps
        self.sums_at = {steps0: self._sums()}
        result = super().window(seconds)
        # the load stays on after the close: cut the sums where the
        # window's own counters were cut
        end = self.sums_at[steps0 + result.counters["device_steps"]]
        for name, a, b in zip(SERVER_SUMS, self.sums_at[steps0], end):
            result.counters[name] = b - a
        if result.counters["moe_layer_steps"]:
            c = result.counters
            print(f"window: experts hit a sparse layer and step "
                  f"{c['experts_hit_sum'] / c['moe_layer_steps']:.2f} of "
                  f"{self.m['num_experts']}, the fullest took "
                  f"{c['expert_load_max_sum'] / c['moe_layer_steps']:.2f} "
                  f"tokens")
        return result

    def readings(self, control: str = "") -> Dict:
        sample = self.sample()
        wrong = sum(1 for t in self.finished
                    if not t.failed
                    and len(t.seq.generated) != t.plan.output_len)
        self.free_program()
        n_out = max(o for _, o in self.plan.pairs + [self.plan.warm_pair])
        want = self.m["serve"]["check_requests"]
        limit = self.m["limits"]["mean_logit_gap"]
        tokens = sum(len(s["served"]) for s in sample)
        what = (f"mean gap of a served token's logit below the "
                f"reference's best ({tokens} tokens of {len(sample)} "
                f"requests)")

        def mean_gap(control: str) -> float:
            if not sample:
                return float("nan")
            gap, margin = pattern_check.gaps(self.key, self.m, sample,
                                             n_out, control)
            side = control or "program"
            print(f"readings {side}: the token is the reference's first "
                  f"at {100 * np.mean(gap == 0):.2f} % of {len(gap)} "
                  f"positions; mean gap {np.mean(gap):.6g}, widest "
                  f"{gap.max():.6g}")
            for at in NEAR_TIE_MARGINS:
                g, share = pattern_check.widest_gap(gap, margin, at)
                print(f"readings {side}: routing margins under {at:g} "
                      f"left out ({100 * share:.2f} % of positions): "
                      f"widest gap {g:.6g}")
            return float(np.mean(gap))

        out = {"program": [
            Check("the traffic ran out before the window closed",
                  int(self.ran_out), 0),
            Check("finished requests with a wrong token count", wrong, 0),
            Check("requests compared short of the sample asked",
                  want - len(sample), 0),
            Check(what, mean_gap(""), limit)]}
        if control:
            out["control"] = [Check(what, mean_gap(control), limit)]
        return out
