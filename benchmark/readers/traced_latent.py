"""A decode step of a latent-attention decoder with a held share of
routed experts (`family: latent_moe_lm`), or a kernel inside it, against
its roofline, %, on `traced_roofline.py`'s plan: the WORK from the spans
of the steps launched inside the traced `[lo, hi]` (`hvd.serve.launch`:
`rows`, `live_tokens`; `hvd.serve.observe` of the same `dstep`:
`experts_hit`, `pairs_here`), their mean set against the TIME of the
decode program's runs inside the same `[lo, hi]` (the module of prefix
`match` run most often); never a window's sum over a traced time.

`kind`, with the count it takes from `lib/counts_latent.py`:
  "step"  the whole step: weights outside the experts once, the DISTINCT
          held experts hit, the live tokens' latents and shared keys in
          every layer, over the memory bandwidth;
  "attn"  the operations named `op` (the read of the cache, a layer-call
          each) inside the decode program's runs against the larger of
          the live tokens' bytes and the absorbed read's operations,
          every layer;
  "gmm"   the operations named `op` (the grouped products) against the
          larger of the hit experts' bytes with `pairs_here` rows in and
          out, and the pairs' operations.

None where there is no trace, the configuration is of another family or
the spans lack the arguments (a program from before they carried them).
Spans that carry the work and no run of the program, or no operation of
that name, raise: the names are part of the yardstick."""
from benchmark.lib import counts_latent
from benchmark.readers import module_time
from benchmark.readers.traced_roofline import (mean_work, op_time_in_runs,
                                               traced_steps)
from benchmark.reduce import program_spans

FAMILY = "latent_moe_lm"


def _step(ctx, w):
    return counts_latent.decode_step_bytes(
        ctx.config, w["experts_hit"], w["live_tokens"],
        ctx.config["serve"]["weights_dtype"]) / ctx.peaks["hbm_bytes_per_s"]


def _attn(ctx, w):
    m = ctx.config
    return m["num_hidden_layers"] * counts_latent.attn_read_seconds(
        m, w["live_tokens"], ctx.peaks, m["serve"]["weights_dtype"])


def _gmm(ctx, w):
    m = ctx.config
    return max(
        counts_latent.grouped_product_bytes(
            m, w["experts_hit"], w["pairs_here"],
            m["serve"]["weights_dtype"]) / ctx.peaks["hbm_bytes_per_s"],
        counts_latent.expert_flops(m, w["pairs_here"])
        / ctx.peaks["bf16_flops_per_s"])


#: kind -> the arguments it needs, the least seconds a step of that mean
#: work could take
KINDS = {
    "step": (("experts_hit", "live_tokens"), _step),
    "attn": (("live_tokens",), _attn),
    "gmm": (("experts_hit", "pairs_here"), _gmm),
}


def read(ctx, match: str, kind: str, op: str = None):
    args, least_s = KINDS[kind]
    t = ctx.trace
    if t is None or ctx.config.get("family") != FAMILY:
        return None
    work = mean_work(traced_steps(
        program_spans.of_cell(ctx.cell["name"]), t.lo, t.hi), args)
    if work is None:
        return None
    runs = module_time.picked_runs(ctx, match, "most_run")
    if not runs:
        raise RuntimeError(
            f"the traced window's spans carry {sorted(work)} and the "
            f"trace holds no run of a program named {match!r}*")
    busy = sum(runs)
    if op is not None:
        busy = op_time_in_runs(t, match, op)
        if not busy:
            raise RuntimeError(
                f"the traced steps carry {sorted(work)} and the decode "
                f"program ran no operation named {op!r}")
    return 100.0 * least_s(ctx, work) / (busy / len(runs))
