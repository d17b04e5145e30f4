"""A decoder of latent-attention layers with group-routed experts of
which this chip holds a share (`family: latent_moe_lm`) served through
`InferenceServer` (serve/server.py): one compressed latent and one shared
key a token in pages, a prompt prefilled in the expanded form and a step
read in the absorbed form, top-k of the router's groups through one
grouped product over the held experts, continuous batching, greedy
tokens, bf16 weights.

The driving of the server, the window, the sampling of finished requests
and the timeline are `lm_serve.Runner`'s, unchanged.  What differs is
what is built (the configuration's one latent kind, the weight tree
stacked by kind of layer), the sums a step that the window's counters do
not have (what the expert layers counted, and the pairs the stepped rows
routed in all), cut at the window's `device_steps`, and the plain
reference the served tokens are held against, by the MEAN gap, as
`pattern_serve.py` does and for its reason (`reference/latent_check.py`).
"""
from __future__ import annotations

import collections
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import traffic as traffic_mod
from benchmark.lib import weights, weights_latent
from benchmark.lib.harness import Check
from benchmark.reference import latent_check
from benchmark.runners import lm_serve

#: the server's running sums a step is noted with (0 where a program has
#: no such counter, as the parent of the PR that brought `pairs_here`)
SERVER_SUMS = ("experts_hit_sum", "expert_load_max_sum", "pairs_here_sum",
               "moe_layer_steps")


def transformer_config(m: Dict, dtype=jnp.bfloat16):
    """The program's configuration for file `m`: one latent kind of
    attention layer, the leading dense layers, then routed experts in
    groups of which `experts_held` are here."""
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.transformer import LatentSpec, Rotary

    n, rs = m["num_hidden_layers"], m["rope_scaling"]
    if rs["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rs['rope_type']!r}: yarn is written")
    mscale = lambda x: 0.1 * x * np.log(rs["factor"]) + 1.0
    spec = LatentSpec(
        n_heads=m["num_attention_heads"], q_rank=m["q_lora_rank"],
        kv_rank=m["kv_lora_rank"], nope_dim=m["qk_nope_head_dim"],
        rope_dim=m["qk_rope_head_dim"], v_dim=m["v_head_dim"],
        rotary=Rotary(
            theta=float(m["rope_theta"]), yarn_factor=float(rs["factor"]),
            yarn_original=rs["original_max_position_embeddings"],
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            attention_factor=float(mscale(rs["mscale"])
                                   / mscale(rs["mscale_all_dim"]))),
        scale_factor=float(mscale(rs["mscale_all_dim"]) ** 2))
    dense = m["first_k_dense_replace"]
    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=spec.n_heads, d_head=spec.v_dim,
        d_ff=m["intermediate_size"], n_layers=n, compute_dtype=dtype,
        layer_attn=(weights_latent.KIND,) * n,
        layer_mlp=("dense",) * dense + ("experts",) * (n - dense),
        attn_specs=((weights_latent.KIND, spec),),
        n_experts=weights_latent.router_width(m),
        experts_per_token=m["num_experts_per_tok"],
        expert_ff=m["moe_intermediate_size"],
        shared_ff=m["n_shared_experts"] * m["moe_intermediate_size"],
        routed_scale=float(m["routed_scaling_factor"]),
        experts_held=weights_latent.held(m), expert_bias=True,
        route_eps=float(m["assumed"]["route_eps"]),
        route_groups=m["n_group"], route_groups_kept=m["topk_group"])


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
            lambda k: weights_latent.params(k, m, jnp.bfloat16))(self.key)
        sv = tr["server"]
        self.server = InferenceServer(
            params, tcfg, max_seq_tokens=sv["max_seq_tokens"],
            max_batch=sv["max_batch"], page_tokens=m["serve"]["page_tokens"],
            pool_pages=sv.get("pool_pages"))
        del params
        self.by_id = {}
        self.finished = []
        self.ended = set()
        self.pairs_sum = 0
        self.prefill_tokens = 0
        self.ran_out = False
        self.pending = collections.deque()
        self.max_group = int(tr["ramp"]["max_group"])
        #: device_steps -> the sums as they stood after that step
        self.sums_at: Dict[int, tuple] = {}
        self._ramp()

    def _sums(self) -> tuple:
        srv = self.server
        return tuple(getattr(srv, n, 0) for n in SERVER_SUMS) + (
            self.pairs_sum,)

    def _step(self, clock) -> None:
        srv, m = self.server, self.m
        occupancy = srv.occupancy_sum
        super()._step(clock)
        # every row stepped routed experts_per_token pairs a sparse
        # layer, here or elsewhere
        rows = round((srv.occupancy_sum - occupancy) * srv.max_batch)
        self.pairs_sum += rows * m["num_experts_per_tok"] * (
            m["num_hidden_layers"] - m["first_k_dense_replace"])
        self.sums_at[srv.device_steps] = self._sums()

    def window(self, seconds: float):
        steps0 = self.server.device_steps
        self.sums_at = {steps0: self._sums()}
        result = super().window(seconds)
        # the load stays on after the close: cut the sums where the
        # window's own counters were cut
        end = self.sums_at[steps0 + result.counters["device_steps"]]
        for name, a, b in zip(SERVER_SUMS + ("pairs_sum",),
                              self.sums_at[steps0], end):
            result.counters[name] = b - a
        c = result.counters
        if c["moe_layer_steps"]:
            print(f"window: held experts hit a sparse layer and step "
                  f"{c['experts_hit_sum'] / c['moe_layer_steps']:.2f} of "
                  f"{self.m['n_routed_experts']}, the fullest took "
                  f"{c['expert_load_max_sum'] / c['moe_layer_steps']:.2f} "
                  f"tokens; {c['pairs_here_sum']} of {c['pairs_sum']} "
                  f"pairs lay here")
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
            gap = latent_check.gaps(self.key, self.m, sample, n_out, control)
            print(f"readings {control or 'program'}: the token is the "
                  f"reference's first at {100 * np.mean(gap == 0):.2f} % "
                  f"of {len(gap)} positions; mean gap {np.mean(gap):.6g}, "
                  f"widest {gap.max():.6g}")
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
