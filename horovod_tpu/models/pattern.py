"""Training a model whose layers follow a PATTERN (`TransformerConfig`,
"a layer PATTERN"): the forward pass over full sequences, the loss and
the train step `make_train_step` hands out for such a model.

Block l: `h = x + Mixer_l(norm1(x))`, `y = h + FFN_l(norm2(h))`.  The
mixer is by the layer's kind (`cfg.layer_attn[l]`, its spec in
`cfg.attn_specs`): causal softmax attention (`attention_mixer`: GQA, q
and k normed a head where the spec says so, the kind's rotary form,
through the flash kernel of ops/flash_attention.py from 128 tokens on,
so that no [H, T, T] scores are kept, forward or backward) or a gated
short convolution (`conv_mixer`, a `ConvSpec`).  The FFN is the dense
SwiGLU (`_mlp_block`) or the routed experts of models/experts.py
(`expert_layer_train`: the experts held here, no exchange).

The layers are unrolled as models/decode.py's `_pattern_walk` unrolls
them, over the same tree (`attn[t]`, `mlp[m]` stacked by kind), each
under `jax.checkpoint`, always: a layer keeps its input and nothing
else for the backward pass.  The loss is `_loss_shard`'s (logsumexp
minus the picked logit over the tied embedding), taken a sequence at a
time and recomputed in the backward pass, so that one sequence's
float32 logits are held and not the batch's.  No auxiliary loss: a
router learns through the weights of the experts it chose.

On a mesh the model is trained over `dp` alone (every replica holds the
same `experts_held`; the gradient exchange is the transpose of the
replicated parameters, as for a uniform model).  `tp`, `sp`, `pp` and
`ep` over a pattern, an attention window, a gate a head and a shared
expert are not run here and refuse by name.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..common.exceptions import HorovodTpuError
from ..parallel import sequence as seq_mod
from . import experts as experts_mod
from .decode import _flash_prompt, _rotate
from .transformer import (ConvSpec, TransformerConfig, _mlp_block,
                          _rmsnorm, traced_step)


def refuse_untrained(cfg: TransformerConfig, mesh) -> None:
    """What of a patterned model the training path does not run."""
    def no(what: str):
        raise HorovodTpuError(
            f"make_train_step: a model with a layer pattern is trained "
            f"without {what}; {what} over a pattern is not run here")
    for axis in ("tp", "sp", "pp", "ep"):
        if mesh.shape.get(axis, 1) > 1:
            no(f"a {axis} axis (the mesh is dp alone)")
    specs = dict(cfg.attn_specs)
    if cfg.latent_kinds():
        no("a latent kind of attention layer (a LatentSpec: "
           f"{', '.join(cfg.latent_kinds())}; no backward pass through the "
           "expanded form is written)")
    if any(getattr(specs[t], "window", 0) for t in cfg.attn_kinds()):
        no("an attention window")
    if cfg.attn_gate:
        no("a gate a head on the attention output")
    if cfg.shared_ff and "experts" in cfg.layer_mlp:
        no("a shared expert")


def conv_mixer(lp: Dict, h, cfg: TransformerConfig):
    """The gated short convolution on normed h [B, T, D]: `(b, c, u) =
    split3(h W_in)`; `g = b * u`; `v_t = sum_j w[:, j] g_{t-(taps-1)+j}`
    with g zero before the sequence's start (depthwise, causal: a
    channel sees its own last `taps` positions of its own row); out
    `(c * v) W_out`.  No bias."""
    dt = cfg.compute_dtype
    T, taps = h.shape[1], lp["w_conv"].shape[-1]
    z = jnp.einsum("btd,de->bte", h, lp["w_in"].astype(dt))
    b, c, u = (a.astype(jnp.float32) for a in jnp.split(z, 3, axis=-1))
    g = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = lp["w_conv"].astype(jnp.float32)
    v = sum(w[:, j] * g[:, j:j + T] for j in range(taps))
    return jnp.einsum("btd,de->bte", (c * v).astype(dt),
                      lp["w_out"].astype(dt))


def attention_mixer(lp: Dict, h, positions, kcfg: TransformerConfig,
                    qk_norm: bool):
    """Causal GQA over the whole sequence on normed h [B, T, D]; `kcfg`
    is the kind's uniform configuration (`cfg.kind_cfg`)."""
    dt = kcfg.compute_dtype
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dt))
    if qk_norm:
        q = _rmsnorm(lp["q_norm"]["scale"], q)
        k = _rmsnorm(lp["k_norm"]["scale"], k)
    q = _rotate(q, positions, kcfg).astype(dt)
    k = _rotate(k, positions, kcfg).astype(dt)
    if h.shape[1] >= 128:
        o = _flash_prompt(q, k, v, None)
    else:
        o = seq_mod.full_attention(q, k, v, causal=True)
    return jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"].astype(dt))


def _layer(ap: Dict, mp: Dict, x, positions, *, cfg: TransformerConfig,
           t: str, m: str):
    """One block: (x [B, T, D], counts [len(TRAINED)] int32 or None)."""
    spec = dict(cfg.attn_specs)[t]
    h = _rmsnorm(ap["ln1"]["scale"], x)
    if isinstance(spec, ConvSpec):
        with jax.named_scope("hvd.conv"):
            x = x + conv_mixer(ap, h, cfg).astype(x.dtype)
    else:
        with jax.named_scope("hvd.attn"):
            x = x + attention_mixer(ap, h, positions, cfg.kind_cfg(t),
                                    spec.qk_norm).astype(x.dtype)
    if m == "dense":
        return _mlp_block(mp, x, cfg, None), None
    B, T, D = x.shape
    h = _rmsnorm(mp["ln2"]["scale"], x).reshape(B * T, D)
    out, counts = experts_mod.expert_layer_train(
        mp, h.astype(cfg.compute_dtype), cfg)
    return x + out.reshape(B, T, D).astype(x.dtype), counts


def pattern_forward(params: Dict, tokens, cfg: TransformerConfig):
    """tokens [B, T] -> (x [B, T, D] before the final norm, counts
    [sparse layers, len(TRAINED)] int32); every layer under
    `jax.checkpoint`."""
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    positions = jnp.arange(tokens.shape[1])
    seen, counts = {}, []
    for t, m in zip(cfg.layer_attn, cfg.layer_mlp):
        j, jm = seen.get(t, 0), seen.get(m, 0)
        seen[t], seen[m] = j + 1, jm + 1
        ap = jax.tree_util.tree_map(lambda p: p[j], params["attn"][t])
        mp = jax.tree_util.tree_map(lambda p: p[jm], params["mlp"][m])
        x, c = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, t=t, m=m))(ap, mp, x, positions)
        if c is not None:
            counts.append(c)
    if not counts:
        return x, jnp.zeros((0, len(experts_mod.TRAINED)), jnp.int32)
    return x, jnp.stack(counts)


def _row_loss(embed, scale, x, targets, dt):
    """Summed cross-entropy of one sequence x [T, D]."""
    logits = jnp.einsum("td,vd->tv", _rmsnorm(scale, x).astype(dt),
                        embed.astype(dt),
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


def pattern_loss_shard(params: Dict, tokens, targets,
                       cfg: TransformerConfig, dp: bool):
    """Per-shard (loss, counts), both replicated over `dp`: the mean
    cross-entropy over every replica's tokens; of the counts, the
    fullest replica's `experts_hit` and `expert_load_max` and the
    replicas' summed `pairs_here` and `rows_worked`."""
    x, counts = pattern_forward(params, tokens, cfg)
    row = jax.checkpoint(functools.partial(
        _row_loss, params["embed"], params["final_norm"]["scale"],
        dt=cfg.compute_dtype))
    total = jnp.sum(lax.map(lambda a: row(*a), (x, targets)))
    count = jnp.asarray(targets.size, jnp.float32)
    if dp:
        total, count = lax.psum((total, count), "dp")
        n = experts_mod.TRAINED.index("pairs_here")
        counts = jnp.concatenate(
            [lax.pmax(counts[:, :n], "dp"), lax.psum(counts[:, n:], "dp")],
            axis=1)
    return total / count, counts


def freeze(cfg: TransformerConfig, updates: Dict) -> Dict:
    """The router's bias takes no optimizer step (its gradient is zero
    by construction; a decoupled weight decay would still move it)."""
    if not (cfg.expert_bias and "experts" in cfg.layer_mlp):
        return updates
    mlp = dict(updates["mlp"])
    mlp["experts"] = dict(
        mlp["experts"],
        router_bias=jnp.zeros_like(mlp["experts"]["router_bias"]))
    return dict(updates, mlp=mlp)


def make_pattern_train_step(mesh, cfg: TransformerConfig, optimizer):
    """`make_train_step` for a patterned model: (step, shard_state,
    shard_lm_batch).  step(params, opt_state, (tokens, targets)) ->
    (params, opt_state, loss) and, for a model with routed experts, a
    fourth value: `TRAINED` a sparse layer, [sparse layers, len(TRAINED)]
    int32, left on the device (no sync of its own)."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    refuse_untrained(cfg, mesh)
    dp = mesh.shape.get("dp", 1) > 1
    data_spec = P("dp" if dp else None, None)
    sparse = "experts" in cfg.layer_mlp

    def loss_fn(params, tokens, targets):
        body = lambda p, t, y: pattern_loss_shard(p, t, y, cfg, dp)
        return shard_map(
            body, mesh=mesh, in_specs=(P(), data_spec, data_spec),
            out_specs=(P(), P()), check_vma=False)(params, tokens, targets)

    def train_step(params, opt_state, batch):
        tokens, targets = batch
        (loss, counts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                        freeze(cfg, updates))
        if sparse:
            return params, opt_state, loss, counts
        return params, opt_state, loss

    replicated = NamedSharding(mesh, P())

    def shard_state(params, opt_state):
        return jax.device_put((params, opt_state), replicated)

    def shard_lm_batch(batch):
        return jax.device_put(tuple(batch), NamedSharding(mesh, data_spec))

    return traced_step(jax.jit(train_step, donate_argnums=(0, 1))), \
        shard_state, shard_lm_batch

