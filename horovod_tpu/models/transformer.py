"""Flagship decoder-only transformer LM, sharded over dp/tp/pp/ep/sp.

Beyond-parity model (the reference is DP-only, SURVEY.md §2.6): this LM
exercises the whole parallelism substrate — tensor-parallel attention/MLP
(Megatron-style column/row splits with psum over `tp`), ring-attention or
Ulysses sequence parallelism over `sp`, Switch-MoE expert parallelism
over `ep`, GPipe pipeline over `pp`, and data parallelism over `dp` with
gradient reduction fused into the backward pass by shard_map's transpose
(replicated in_spec → psum), the SPMD analog of
hvd.DistributedOptimizer's allreduce.

Design: ONE shard_map over the full mesh; every collective is explicit
(`psum`/`ppermute`/`all_to_all` on named axes riding ICI).  bf16 compute,
f32 params/accumulation.  `*_ref` functions are the single-device oracle
the tests compare against.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common.exceptions import HorovodTpuError
from ..parallel import moe as moe_mod
from ..parallel import sequence as seq_mod
from ..utils.timeline import span
from . import layers as L


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A rotary embedding's form.  `share` of a head's dims rotate (the
    leading ones, as interleaved pairs) and the rest pass unrotated.
    `yarn_factor` > 0 is YaRN as published: pair i of n turns at
    `(f_i / factor) r_i + f_i (1 - r_i)` with `f_i = theta^(-i/n)` and
    `r_i` a ramp from pair `lo` to pair `hi`, the pairs that turn
    `beta_fast` and `beta_slow` times over `yarn_original` positions;
    cos and sin are multiplied by `attention_factor`."""
    theta: float = 10000.0
    share: float = 1.0
    yarn_factor: float = 0.0
    yarn_original: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0

    def tables(self, d_head: int):
        """(freqs [n] float64 numpy, n): the angle a position each of the
        n = d_head * share / 2 rotated pairs turns by."""
        import numpy as np
        n = int(d_head * self.share) // 2
        f = self.theta ** (-np.arange(n, dtype=np.float64) / n)
        if self.yarn_factor:
            def pair(beta):     # the pair that turns `beta` times
                return n * math.log(self.yarn_original
                                    / (2 * math.pi * beta)) \
                    / math.log(self.theta)
            lo = max(math.floor(pair(self.yarn_beta_fast)), 0)
            hi = min(math.ceil(pair(self.yarn_beta_slow)), 2 * n - 1)
            r = np.clip((np.arange(n) - lo) / max(hi - lo, 1e-3), 0, 1)
            f = (f / self.yarn_factor) * r + f * (1 - r)
        return f, n


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """What one kind of attention layer of a patterned model has of its
    own: query heads, window (0 = the whole context), rotary form, and
    whether q and k are RMS-normed a head (a learned scale over `d_head`,
    before the rotary embedding)."""
    n_heads: int
    window: int = 0
    rotary: Rotary = Rotary()
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """A gated short convolution as a patterned model's token mixer
    (models/pattern.py, `conv_mixer`): `(b, c, u) = split3(h W_in)`, a
    causal depthwise convolution of `taps` taps a channel over `b * u`,
    then `(c * v) W_out`.  No heads, no cache of keys: trained
    (`make_train_step`), not yet served."""
    taps: int = 3


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """A LATENT kind of attention layer (multi-head latent attention, as
    `deepseek_v3` publishes it; models/decode.py, "Latent attention"):
    queries through a rank of `q_rank` (normed) to `n_heads` heads of
    `nope_dim + rope_dim`; keys and values through ONE normed latent of
    `kv_rank` a token, expanded a head to `nope_dim` of key and `v_dim` of
    value, beside ONE rotated key of `rope_dim` that every head shares.
    The cache holds the latent and the shared key, `kv_rank + rope_dim`
    numbers a token and layer whatever the heads (the key in `key_lanes`
    lanes).  `rotary` turns the `rope_dim` part (all of it); the softmax
    scale is `(nope_dim + rope_dim) ** -0.5 * scale_factor` (YaRN's
    `mscale` squared, where the model has one).  No window, no q/k norm
    a head.  Served and generated; not trained."""
    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rotary: Rotary = Rotary()
    scale_factor: float = 1.0
    window = 0              # what the other kinds' readers ask of a spec
    qk_norm = False

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5 * self.scale_factor

    @property
    def key_lanes(self) -> int:
        """The width the shared key is CACHED in: `rope_dim` numbers and
        zeros up to whole tiles of 128 lanes.  The chip lays a leaf of 64
        in tiles of (8, 128) and pads it to that in HBM whatever its
        shape says, and the decode kernel's copies are whole tiles
        (Mosaic refuses a slice of 64 lanes): the zeros cost no byte that
        would not be there, and add nothing to a score."""
        return -(-self.rope_dim // 128) * 128


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    d_head: int = 64
    d_ff: int = 2048
    n_layers: int = 8
    moe_every: int = 0          # 0 = dense; k = every k-th layer is MoE
    n_experts: int = 8
    capacity_factor: float = 1.25
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    attn_impl: str = "ring"     # "ring" | "ulysses" (used when sp > 1)
    aux_loss_weight: float = 0.01
    n_kv_heads: int = 0         # 0 = MHA; else GQA/MQA kv head count
    attn_window: int = 0        # 0 = full causal; else sliding window
    # "softmax" | "retention".  A power-retention layer (models/decode.py,
    # "Power retention") weighs key j for query t by
    # decay(j..t) * (q.k / sqrt(d_head)) ** 2 with no softmax, q and k
    # normed per head; its cache is a fixed state a row, not a ring of
    # keys and values.  Served and generated; not trained
    # (make_train_step refuses it by name).
    attn_kind: str = "softmax"
    state_dtype: Any = jnp.float32   # what the retention state is held in
    # -- a layer PATTERN (models/decode.py, "Patterned models") ----------
    # `layer_attn[l]` names layer l's kind of attention, a key of
    # `attn_specs` (heads, window and rotary form by kind: `n_heads`,
    # `attn_window` and `rope_theta` above then say nothing), and
    # `layer_mlp[l]` its MLP: "dense" (SwiGLU of `d_ff`) or "experts":
    # `experts_per_token` of `n_experts` routed SwiGLU experts of width
    # `expert_ff`, sigmoid scores renormalised over the chosen ones and
    # times `routed_scale`, the weight on the output, beside one shared
    # SwiGLU of `shared_ff` (0: none).  `experts_held` is the range
    # [lo, hi) of experts whose weights are here (None: all); the router
    # keeps its width and the layer computes its own experts' part of
    # the result.  `attn_gate`: a sigmoid gate a head on the attention
    # output.  `expert_bias`: the router carries a `router_bias`
    # [n_experts] that takes part in the CHOICE of experts only (no
    # gradient, no optimizer step); `route_eps` is added to the sum the
    # chosen scores are renormalised by.  `route_groups` > 0: the experts
    # are that many groups of equal size, a group scores the sum of its
    # two best choice scores, and a token chooses among the experts of
    # its `route_groups_kept` best groups only (0: no groups).  A kind
    # whose spec is a `ConvSpec` is a gated short convolution in the
    # attention's place; one whose spec is a `LatentSpec` keeps one
    # compressed latent a token (served and generated, not trained).
    # Empty tuples: one kind for all layers, everything as it was.
    # Served and generated (all but a convolution kind and q/k norm,
    # which refuse by name), and trained on a `dp` mesh
    # (make_train_step -> models/pattern.py: every layer recomputed,
    # attention through the flash kernel; a window, an attention gate, a
    # shared expert, a latent kind and tp/sp/pp/ep over a pattern refuse
    # by name).
    # `moe_every` / `capacity_factor` still mean the uniform model's
    # top-1 layer.
    layer_attn: Tuple[str, ...] = ()
    layer_mlp: Tuple[str, ...] = ()
    # AttnSpec | ConvSpec | LatentSpec
    attn_specs: Tuple[Tuple[str, Any], ...] = ()
    attn_gate: bool = False
    experts_per_token: int = 1
    expert_ff: int = 0
    shared_ff: int = 0
    routed_scale: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    expert_bias: bool = False
    route_eps: float = 0.0
    route_groups: int = 0
    route_groups_kept: int = 0
    # A latent kind's spec, in the uniform configuration `kind_cfg` makes
    # of such a kind (its layers and its cache are sized from it); a
    # model names the kind in `attn_specs` and sets nothing here.
    latent: Optional[LatentSpec] = None
    # The rotary form of a uniform model whose form is not the plain one
    # (`rope_theta` alone); a patterned model's layers get theirs from
    # `attn_specs`.
    rotary: Optional[Rotary] = None
    # "auto": the prompt's attention is dense under T 16384 and the flash
    # kernel from there (parallel/sequence.py).  "flash": the kernel at
    # every length from 128 on, the prompt padded to its tile; a
    # patterned model's layers take it, so that no [H, T, T] is kept.
    prompt_attention: str = "auto"

    def __post_init__(self):
        self._check_pattern()
        if self.attn_kind not in ("softmax", "retention"):
            raise ValueError(
                f"attn_kind must be 'softmax' or 'retention', got "
                f"{self.attn_kind!r}")
        if self.attn_kind == "retention":
            if self.attn_window:
                raise ValueError(
                    "a retention layer has no window (its decay forgets); "
                    "attn_window must be 0")
            if self.d_head % 2:
                raise ValueError(
                    f"retention needs an even d_head, got {self.d_head}")
        if self.attn_window < 0:
            raise ValueError(
                f"attn_window must be >= 0, got {self.attn_window}")
        if self.n_kv_heads < 0 or (
                self.n_kv_heads and self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"n_kv_heads ({self.n_kv_heads}) must be 0 (MHA) or a "
                f"divisor of n_heads ({self.n_heads})")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    # -- the pattern -----------------------------------------------------

    @property
    def patterned(self) -> bool:
        return bool(self.layer_attn)

    def _check_pattern(self) -> None:
        if self.prompt_attention not in ("auto", "flash"):
            raise ValueError(
                f"prompt_attention must be 'auto' or 'flash', got "
                f"{self.prompt_attention!r}")
        if not self.patterned:
            if self.layer_mlp or self.attn_specs:
                raise ValueError(
                    "layer_mlp and attn_specs come with layer_attn (a "
                    "kind of attention a layer)")
            return
        if self.attn_kind != "softmax" or self.moe_every:
            raise ValueError(
                "a layer pattern is of softmax layers, with its own "
                "experts: attn_kind 'softmax' and moe_every 0")
        specs = dict(self.attn_specs)
        if len(self.layer_attn) != self.n_layers or \
                len(self.layer_mlp) != self.n_layers:
            raise ValueError(
                f"layer_attn and layer_mlp name every layer: "
                f"{len(self.layer_attn)} and {len(self.layer_mlp)} names "
                f"for n_layers {self.n_layers}")
        for t in self.layer_attn:
            if t not in specs:
                raise ValueError(
                    f"layer_attn names {t!r}, attn_specs has "
                    f"{sorted(specs)}")
        for t, spec in specs.items():
            if isinstance(spec, ConvSpec):
                if spec.taps < 1:
                    raise ValueError(
                        f"attn_specs[{t!r}]: a convolution of "
                        f"{spec.taps} taps")
                continue
            if isinstance(spec, LatentSpec):
                if spec.rope_dim % 2 or min(
                        spec.n_heads, spec.q_rank, spec.kv_rank,
                        spec.nope_dim, spec.rope_dim, spec.v_dim) < 1:
                    raise ValueError(
                        f"attn_specs[{t!r}]: a latent layer's heads, "
                        f"ranks and widths are >= 1 and its rope_dim "
                        f"even, got {spec}")
                continue
            if spec.n_heads % self.kv_heads:
                raise ValueError(
                    f"attn_specs[{t!r}]: {spec.n_heads} heads over "
                    f"{self.kv_heads} kv heads")
            if int(self.d_head * spec.rotary.share) % 2:
                raise ValueError(
                    f"attn_specs[{t!r}]: rotary share "
                    f"{spec.rotary.share} of d_head {self.d_head} is no "
                    "whole number of pairs")
        for m in self.layer_mlp:
            if m not in ("dense", "experts"):
                raise ValueError(
                    f"layer_mlp is 'dense' or 'experts', got {m!r}")
        if "experts" in self.layer_mlp:
            lo, hi = self.held
            if not (0 <= lo < hi <= self.n_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is no range of "
                    f"the {self.n_experts} experts")
            if not 1 <= self.experts_per_token <= self.n_experts \
                    or self.expert_ff < 1:
                raise ValueError(
                    f"experts_per_token {self.experts_per_token} of "
                    f"{self.n_experts} experts of width {self.expert_ff}")
            G, kept = self.route_groups, self.route_groups_kept
            if G and (self.n_experts % G or self.n_experts // G < 2
                      or not 1 <= kept <= G
                      or self.experts_per_token > kept * self.n_experts // G):
                raise ValueError(
                    f"route_groups {G} with route_groups_kept {kept}: "
                    f"{self.n_experts} experts make no such groups of 2 "
                    f"or more, or the kept ones hold fewer than "
                    f"experts_per_token {self.experts_per_token}")

    @property
    def held(self) -> Tuple[int, int]:
        """[lo, hi): the experts whose weights are here."""
        return self.experts_held or (0, self.n_experts)

    def attn_kinds(self) -> Tuple[str, ...]:
        """The kinds of attention layer (or of the convolution in its
        place), in order of first use."""
        return tuple(dict.fromkeys(self.layer_attn))

    def conv_kinds(self) -> Tuple[str, ...]:
        specs = dict(self.attn_specs)
        return tuple(t for t in self.attn_kinds()
                     if isinstance(specs[t], ConvSpec))

    def latent_kinds(self) -> Tuple[str, ...]:
        specs = dict(self.attn_specs)
        return tuple(t for t in self.attn_kinds()
                     if isinstance(specs[t], LatentSpec))

    def mlp_kinds(self) -> Tuple[str, ...]:
        return tuple(dict.fromkeys(self.layer_mlp))

    def kind_cfg(self, kind: str) -> "TransformerConfig":
        """The uniform configuration of this model's `kind` attention
        layers alone: what `_decode_layer` and `_prefill_layer` are
        handed for such a layer, and what sizes its part of the cache
        (`n_layers` of it is the number of such layers)."""
        return _kind_cfg(self, kind)


@functools.lru_cache(maxsize=None)
def _kind_cfg(cfg: TransformerConfig, kind: str) -> TransformerConfig:
    spec = dict(cfg.attn_specs)[kind]
    if isinstance(spec, ConvSpec):
        raise HorovodTpuError(
            f"layer kind {kind!r} is a gated short convolution: it has no "
            "attention configuration; such a model is trained "
            "(make_train_step), not served or generated")
    plain = spec.rotary == Rotary(theta=spec.rotary.theta)
    latent = spec if isinstance(spec, LatentSpec) else None
    return dataclasses.replace(
        cfg, n_heads=spec.n_heads, attn_window=spec.window,
        rope_theta=spec.rotary.theta,
        rotary=None if plain else spec.rotary, latent=latent,
        # one head of latent and shared key, whatever the query heads
        n_kv_heads=1 if latent else cfg.n_kv_heads,
        n_layers=cfg.layer_attn.count(kind), prompt_attention="flash",
        layer_attn=(), layer_mlp=(), attn_specs=())


def _refuse_pattern(cfg: TransformerConfig, what: str) -> None:
    if cfg.patterned:
        raise HorovodTpuError(
            f"{what}: a model with a layer pattern (layer_attn) is not "
            "run here; it is served and generated (InferenceServer, "
            "transformer_generate) and trained through make_train_step "
            "on a dp mesh (models/pattern.py)")


def refuse_unserved(cfg: TransformerConfig, what: str) -> None:
    """What of a layer pattern is trained and not served: a convolution
    kind (no `_Kind` record, no state beside the pages) and q/k norm."""
    specs = dict(cfg.attn_specs)
    if cfg.conv_kinds():
        raise HorovodTpuError(
            f"{what}: this layer pattern has a gated short convolution "
            f"({', '.join(cfg.conv_kinds())}), which is trained "
            "(make_train_step) and not served or generated: a decode "
            "step would need its state of `taps - 1` vectors a row "
            "beside the pages")
    if any(specs[t].qk_norm for t in cfg.attn_kinds()):
        raise HorovodTpuError(
            f"{what}: this layer pattern norms q and k a head, which the "
            "training forward does (make_train_step) and the served "
            "layers do not yet")


# ---------------------------------------------------------------------------
# Init — layer-stacked params [L, ...] (scan- and pipeline-friendly)
# ---------------------------------------------------------------------------

def _pattern_init(key, cfg: TransformerConfig) -> Dict:
    """A patterned model's tree: leaves stacked by layer KIND, `attn[t]`
    over the layers of attention kind t and `mlp[m]` over those of MLP
    kind m, each in the order the layers come.  Normal, 1/sqrt(fan_in);
    the experts' weights are those held here, [n, hi - lo, ...]."""
    D, Dh, Hkv = cfg.d_model, cfg.d_head, cfg.kv_heads
    s_d = 1.0 / math.sqrt(D)

    def norm(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    def swiglu(k, lead, F):
        ks = jax.random.split(k, 3)
        return {"wi": norm(ks[0], lead + (D, F), s_d),
                "wg": norm(ks[1], lead + (D, F), s_d),
                "wd": norm(ks[2], lead + (F, D), 1.0 / math.sqrt(F))}

    params = {
        "embed": norm(jax.random.fold_in(key, 0), (cfg.vocab_size, D), s_d),
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "attn": {}, "mlp": {}}
    for n, t in enumerate(cfg.attn_kinds()):
        ks = jax.random.split(jax.random.fold_in(key, 10 + n), 5)
        Lt, spec = cfg.layer_attn.count(t), dict(cfg.attn_specs)[t]
        if isinstance(spec, ConvSpec):
            params["attn"][t] = {
                "ln1": {"scale": jnp.ones((Lt, D), jnp.float32)},
                "w_in": norm(ks[0], (Lt, D, 3 * D), s_d),
                "w_conv": norm(ks[1], (Lt, D, spec.taps),
                               1.0 / math.sqrt(spec.taps)),
                "w_out": norm(ks[2], (Lt, D, D), s_d)}
            continue
        if isinstance(spec, LatentSpec):
            H, Rq, R = spec.n_heads, spec.q_rank, spec.kv_rank
            dn, dr, dv = spec.nope_dim, spec.rope_dim, spec.v_dim
            ones = lambda n: {"scale": jnp.ones((Lt, n), jnp.float32)}
            params["attn"][t] = {
                "ln1": ones(D), "q_norm": ones(Rq), "kv_norm": ones(R),
                "wq_a": norm(ks[0], (Lt, D, Rq), s_d),
                "wq_b": norm(ks[1], (Lt, Rq, H, dn + dr),
                             1.0 / math.sqrt(Rq)),
                # the latent and, behind it, the shared key before rotation
                "wkv_a": norm(ks[2], (Lt, D, R + dr), s_d),
                # a head's key part, then its value
                "wkv_b": norm(ks[3], (Lt, R, H, dn + dv),
                              1.0 / math.sqrt(R)),
                "wo": norm(ks[4], (Lt, H, dv, D), 1.0 / math.sqrt(H * dv))}
            continue
        H = spec.n_heads
        ap = {"ln1": {"scale": jnp.ones((Lt, D), jnp.float32)},
              "wq": norm(ks[0], (Lt, D, H, Dh), s_d),
              "wk": norm(ks[1], (Lt, D, Hkv, Dh), s_d),
              "wv": norm(ks[2], (Lt, D, Hkv, Dh), s_d),
              "wo": norm(ks[3], (Lt, H, Dh, D), 1.0 / math.sqrt(H * Dh))}
        if cfg.attn_gate:
            ap["w_gate"] = norm(ks[4], (Lt, D, H), s_d)
        if spec.qk_norm:
            for name in ("q_norm", "k_norm"):
                ap[name] = {"scale": jnp.ones((Lt, Dh), jnp.float32)}
        params["attn"][t] = ap
    for n, m in enumerate(cfg.mlp_kinds()):
        k = jax.random.fold_in(key, 20 + n)
        Lm = cfg.layer_mlp.count(m)
        ln2 = {"scale": jnp.ones((Lm, D), jnp.float32)}
        if m == "dense":
            params["mlp"][m] = {"ln2": ln2, **swiglu(k, (Lm,), cfg.d_ff)}
            continue
        ks = jax.random.split(k, 3)
        lo, hi = cfg.held
        mp = {"ln2": ln2,
              "router": norm(ks[0], (Lm, D, cfg.n_experts), s_d),
              "experts": swiglu(ks[1], (Lm, hi - lo), cfg.expert_ff)}
        if cfg.expert_bias:
            # small beside the scores' spread: it flips the closest
            # choices and not all of them
            mp["router_bias"] = norm(jax.random.fold_in(k, 3),
                                     (Lm, cfg.n_experts), 0.01)
        if cfg.shared_ff:
            mp["shared"] = swiglu(ks[2], (Lm,), cfg.shared_ff)
        params["mlp"][m] = mp
    return params


def transformer_init(key, cfg: TransformerConfig) -> Dict:
    if cfg.patterned:
        return _pattern_init(key, cfg)
    keys = jax.random.split(key, 8)
    D, H, Dh, F, Lr = (cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff,
                       cfg.n_layers)
    s_d = 1.0 / math.sqrt(D)
    s_f = 1.0 / math.sqrt(F)
    s_hd = 1.0 / math.sqrt(H * Dh)

    def norm(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    params = {
        "embed": norm(keys[0], (cfg.vocab_size, D), s_d),
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "blocks": {
            "ln1": {"scale": jnp.ones((Lr, D), jnp.float32)},
            "ln2": {"scale": jnp.ones((Lr, D), jnp.float32)},
            "wq": norm(keys[1], (Lr, D, H, Dh), s_d),
            "wk": norm(keys[2], (Lr, D, cfg.kv_heads, Dh), s_d),
            "wv": norm(keys[3], (Lr, D, cfg.kv_heads, Dh), s_d),
            "wo": norm(keys[4], (Lr, H, Dh, D), s_hd),
            "wi": norm(keys[5], (Lr, D, F), s_d),
            "wg": norm(keys[6], (Lr, D, F), s_d),
            "wd": norm(keys[7], (Lr, F, D), s_f),
        },
    }
    if cfg.attn_kind == "retention":
        # The decay gate: one number a kv head and token, through
        # log-sigmoid; the bias starts where a token keeps 0.95 to 0.9995
        # of the state, head by head (zero would halve it every token).
        Hkv = cfg.kv_heads
        params["blocks"]["w_decay"] = norm(
            jax.random.fold_in(key, 98), (Lr, D, Hkv), s_d)
        params["blocks"]["b_decay"] = jnp.broadcast_to(
            jnp.linspace(3.0, 7.5, Hkv, dtype=jnp.float32), (Lr, Hkv))
        # q and k are normed per head over d_head, with a learned scale
        for name in ("q_norm", "k_norm"):
            params["blocks"][name] = {
                "scale": jnp.ones((Lr, Dh), jnp.float32)}
    if cfg.moe_every:
        n_moe = sum(1 for i in range(Lr) if (i + 1) % cfg.moe_every == 0)
        mkeys = jax.random.split(jax.random.fold_in(key, 99), n_moe)
        params["moe"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[moe_mod.moe_init(mkeys[i], cfg.n_experts, D, F)
              for i in range(n_moe)])
    return params


def _is_moe_layer(cfg: TransformerConfig, i: int) -> bool:
    return bool(cfg.moe_every) and (i + 1) % cfg.moe_every == 0


# ---------------------------------------------------------------------------
# Shared layer math (full-array; works on local shards too)
# ---------------------------------------------------------------------------

def _rope(x, positions, theta: float):
    """Rotary embedding: x [B, T, H, Dh], positions [T]."""
    Dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)           # [T, Dh/2]
    x1, x2 = x[..., ::2], x[..., 1::2]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _rmsnorm(scale, x):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)
            * scale).astype(x.dtype)


def _attention_block(lp, x, positions, cfg, tp_axis, sp_axis):
    """Pre-norm attention with RoPE.  lp: this layer's params (unstacked).
    Inside shard_map: heads sharded over tp, sequence over sp."""
    _refuse_pattern(cfg, "the training forward")
    if cfg.attn_kind != "softmax":
        raise HorovodTpuError(
            f"the training forward has no {cfg.attn_kind!r} layer: such "
            "a model is served and generated (models/decode.py), not "
            "trained")
    dt = cfg.compute_dtype
    h = _rmsnorm(lp["ln1"]["scale"], x)
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dt))
    q = _rope(q, positions, cfg.rope_theta).astype(dt)
    k = _rope(k, positions, cfg.rope_theta).astype(dt)
    window = cfg.attn_window or None
    if sp_axis is not None:
        # Ring attention is GQA-native: the ppermute rotates the SMALL
        # Hkv blocks around the ring (ICI bytes / group factor) and the
        # per-pair engines expand heads locally (XLA blockwise) or share
        # them via index maps (flash kernel).  Ulysses all_to_alls over
        # heads, so it needs the full head count — repeat there.
        # Windows ride the XLA blockwise ring's per-pair position bands
        # or Ulysses' locally-full sequence.
        if cfg.attn_impl == "ulysses":
            k, v = seq_mod.repeat_kv(q, k, v)
            o = seq_mod.ulysses_attention_shard(q, k, v, sp_axis,
                                                window=window)
        else:
            o = seq_mod.ring_attention_shard(q, k, v, sp_axis,
                                             window=window)
    else:
        o = seq_mod.full_attention(q, k, v, causal=True, window=window)
    out = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dt))
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)   # row-parallel wo
    return x + out.astype(x.dtype)


def _mlp_block(lp, x, cfg, tp_axis):
    """Pre-norm SwiGLU MLP; d_ff sharded over tp (column wi/wg, row wd)."""
    dt = cfg.compute_dtype
    h = _rmsnorm(lp["ln2"]["scale"], x)
    up = jnp.einsum("btd,df->btf", h, lp["wi"].astype(dt))
    gate = jax.nn.silu(jnp.einsum("btd,df->btf", h, lp["wg"].astype(dt)))
    out = jnp.einsum("btf,fd->btd", up * gate, lp["wd"].astype(dt))
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)
    return x + out.astype(x.dtype)


def _moe_block(mp, scale, x, cfg, ep_axis):
    """MoE layer replacing the MLP; reuses the layer's ln2 scale."""
    h = _rmsnorm(scale, x)
    if ep_axis is not None:
        out, aux = moe_mod.moe_apply_shard(
            mp, h, axis=ep_axis, capacity_factor=cfg.capacity_factor,
            compute_dtype=cfg.compute_dtype)
    else:
        out, aux = moe_mod.moe_apply_dense(
            mp, h, capacity_factor=cfg.capacity_factor,
            compute_dtype=cfg.compute_dtype)
    return x + out.astype(x.dtype), aux["aux_loss"]


# ---------------------------------------------------------------------------
# Reference (single-device) forward — the numerical oracle
# ---------------------------------------------------------------------------

def transformer_ref_apply(params: Dict, tokens, cfg: TransformerConfig):
    """tokens [B, T] → logits [B, T, V]; returns (logits, aux_loss)."""
    _refuse_pattern(cfg, "the training forward")
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    positions = jnp.arange(tokens.shape[1])
    aux_total = jnp.zeros((), jnp.float32)
    moe_idx = 0
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda p: p[i], params["blocks"])
        x = _attention_block(lp, x, positions, cfg, None, None)
        if _is_moe_layer(cfg, i):
            mp = jax.tree_util.tree_map(lambda p: p[moe_idx], params["moe"])
            x, aux = _moe_block(mp, lp["ln2"]["scale"], x, cfg, None)
            aux_total += aux
            moe_idx += 1
        else:
            x = _mlp_block(lp, x, cfg, None)
    x = _rmsnorm(params["final_norm"]["scale"], x)
    # Head matmul in compute_dtype with f32 MXU accumulation: at bf16
    # this is ~4x the f32 matmul rate on v5e and cost 1/3 of the bench
    # step before (r04 profile, docs/PERF_NOTES.md); logits come out
    # f32 either way.
    logits = jnp.einsum("btd,vd->btv", x.astype(cfg.compute_dtype),
                        params["embed"].astype(cfg.compute_dtype),
                        preferred_element_type=jnp.float32)
    return logits, aux_total


def transformer_ref_loss(params: Dict, tokens, targets,
                         cfg: TransformerConfig):
    """Reference next-token loss: fused cross-entropy (logsumexp minus
    the picked logit — identical math to log_softmax + gather without
    materializing the normalized [B, T, V] matrix) plus the weighted
    MoE aux loss.  The ONE definition the bench, the sharded `_loss`,
    and the parity tests all share, so they cannot drift apart."""
    logits, aux = transformer_ref_apply(params, tokens, cfg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - picked)
    if cfg.moe_every:
        loss = loss + cfg.aux_loss_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Sharded forward (inside ONE shard_map over the full mesh)
# ---------------------------------------------------------------------------

def _layer_seq(block_params, moe_params, x, positions, cfg,
               layer_offset: int, n_layers: int,
               tp_axis, sp_axis, ep_axis):
    """Apply `n_layers` consecutive layers starting at global index
    `layer_offset`.  Params carry a leading [n_layers] (and [n_moe]) dim."""
    aux_total = jnp.zeros((), jnp.float32)
    moe_idx = 0
    for j in range(n_layers):
        lp = jax.tree_util.tree_map(lambda p: p[j], block_params)
        x = _attention_block(lp, x, positions, cfg, tp_axis, sp_axis)
        if _is_moe_layer(cfg, layer_offset + j):
            mp = jax.tree_util.tree_map(lambda p: p[moe_idx], moe_params)
            x, aux = _moe_block(mp, lp["ln2"]["scale"], x, cfg, ep_axis)
            aux_total += aux
            moe_idx += 1
        else:
            x = _mlp_block(lp, x, cfg, tp_axis)
    return x, aux_total


def _forward_shard(params, tokens, cfg: TransformerConfig,
                   axes: Dict[str, bool], n_microbatches: int):
    """Per-shard forward.  tokens [B_local, T_local].  Returns
    (x_final [B_local, T_local, D], aux_loss)."""
    tp_axis = "tp" if axes.get("tp") else None
    sp_axis = "sp" if axes.get("sp") else None
    ep_axis = "ep" if axes.get("ep") else None
    pp = axes.get("pp")

    Tl = tokens.shape[1]
    sp_off = (lax.axis_index(sp_axis) * Tl) if sp_axis else 0
    positions = sp_off + jnp.arange(Tl)
    x = params["embed"][tokens].astype(cfg.compute_dtype)

    if not pp:
        x, aux = _layer_seq(
            params["blocks"], params.get("moe"), x, positions, cfg,
            0, cfg.n_layers, tp_axis, sp_axis, ep_axis)
        return x, aux

    # Pipeline: blocks leaves arrive as [1, L/pp, ...] (pp-sharded);
    # aux (MoE balance) loss is not threaded through the pipeline carry —
    # with pp>1 it is omitted (documented limitation).
    from ..parallel.pipeline import gpipe_shard

    blocks = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0),
                                    params["blocks"])
    moe = (jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0),
                                  params["moe"])
           if "moe" in params else None)
    l_per_stage = blocks["wq"].shape[0]
    # The layer pattern must be stage-periodic so every stage runs the
    # same program (checked at trace time by transformer_pspecs).

    def stage_fn(sp_params, h):
        h, _ = _layer_seq(
            sp_params["blocks"], sp_params.get("moe"), h, positions, cfg,
            0, l_per_stage, tp_axis, sp_axis, ep_axis)
        return h

    B = x.shape[0]
    M = n_microbatches
    if B % M != 0:
        raise HorovodTpuError(
            f"local batch {B} not divisible by {M} microbatches")
    x_mb = x.reshape((M, B // M) + x.shape[1:])
    sp_params = {"blocks": blocks}
    if moe is not None:
        sp_params["moe"] = moe
    out = gpipe_shard(stage_fn, sp_params, x_mb, axis="pp")
    x = out.reshape((B,) + out.shape[2:])
    return x, jnp.zeros((), jnp.float32)


def _loss_shard(params, tokens, targets, cfg: TransformerConfig,
                axes: Dict[str, bool], n_microbatches: int):
    """Per-shard scalar loss, replicated via psum over every present
    axis.  With pp, only the last stage's head-path contributes (masking
    prevents the pp-fold gradient overcount through the tied embedding)."""
    x, aux = _forward_shard(params, tokens, cfg, axes, n_microbatches)
    x = _rmsnorm(params["final_norm"]["scale"], x)
    logits = jnp.einsum("btd,vd->btv", x.astype(cfg.compute_dtype),
                        params["embed"].astype(cfg.compute_dtype),
                        preferred_element_type=jnp.float32)
    # Fused cross-entropy: logsumexp - picked logit.  Identical math to
    # log_softmax + gather but never materializes the normalized
    # [B, T, V] matrix (a third of the bench step's time before —
    # r04 profile).
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    ce = lse - picked

    batch_axes = [a for a in ("dp", "ep", "sp", "pp") if axes.get(a)]
    local_sum = jnp.sum(ce)
    local_cnt = jnp.asarray(ce.size, jnp.float32)
    if axes.get("pp"):
        pp_size = lax.psum(1, "pp")
        is_last = (lax.axis_index("pp") == pp_size - 1).astype(jnp.float32)
        local_sum = local_sum * is_last
        local_cnt = local_cnt * is_last
    if batch_axes:
        total = lax.psum(local_sum, tuple(batch_axes))
        count = lax.psum(local_cnt, tuple(batch_axes))
    else:
        total, count = local_sum, local_cnt
    loss = total / count
    if cfg.moe_every and not axes.get("pp"):
        # pmean over every batch-ish axis: aux differs per dp/ep/sp shard
        # (local tokens), and the loss must be replicated so the transpose
        # doesn't overcount the balance gradient.
        aux_axes = tuple(a for a in ("dp", "ep", "sp") if axes.get(a))
        aux_mean = lax.pmean(aux, aux_axes) if aux_axes else aux
        loss = loss + cfg.aux_loss_weight * aux_mean
    return loss


# ---------------------------------------------------------------------------
# Sharding rules + train-step builder
# ---------------------------------------------------------------------------

def stack_for_pipeline(params: Dict, pp: int, cfg: TransformerConfig) -> Dict:
    """Reshape layer-stacked [L, ...] leaves to [pp, L/pp, ...] (and MoE
    [Lm, ...] to [pp, Lm/pp, ...]) for pp-sharded in_specs."""
    if pp <= 1:
        return params
    L = cfg.n_layers
    if L % pp:
        raise ValueError(f"n_layers {L} not divisible by pp {pp}")
    if cfg.moe_every and (L // pp) % cfg.moe_every:
        raise ValueError(
            f"layers-per-stage {L // pp} must be a multiple of "
            f"moe_every {cfg.moe_every} so stages are uniform")
    out = dict(params)
    out["blocks"] = jax.tree_util.tree_map(
        lambda p: p.reshape((pp, L // pp) + p.shape[1:]), params["blocks"])
    if "moe" in params:
        Lm = jax.tree_util.tree_leaves(params["moe"])[0].shape[0]
        out["moe"] = jax.tree_util.tree_map(
            lambda p: p.reshape((pp, Lm // pp) + p.shape[1:]),
            params["moe"])
    return out


def transformer_pspecs(cfg: TransformerConfig, pp: int = 1) -> Dict:
    """PartitionSpec tree matching `transformer_init` output (after
    `stack_for_pipeline` when pp > 1).

    wk/wv shard their head axis over tp like wq; under GQA this
    requires n_kv_heads % tp == 0 (the standard GQA+TP constraint)."""
    from jax.sharding import PartitionSpec as P

    lead = ("pp",) if pp > 1 else ()

    def bspec(*rest):
        return P(*lead, None, *rest)   # [pp?, L(/pp), ...]

    specs = {
        "embed": P(),
        "final_norm": {"scale": P()},
        "blocks": {
            "ln1": {"scale": bspec(None)},
            "ln2": {"scale": bspec(None)},
            "wq": bspec(None, "tp", None),
            "wk": bspec(None, "tp", None),
            "wv": bspec(None, "tp", None),
            "wo": bspec("tp", None, None),
            "wi": bspec(None, "tp"),
            "wg": bspec(None, "tp"),
            "wd": bspec("tp", None),
        },
    }
    if cfg.moe_every:
        specs["moe"] = {
            "gate": {"kernel": bspec(None, None)},
            "wi": bspec("ep", None, None),
            "wo": bspec("ep", None, None),
        }
    return specs


def make_train_step(mesh, cfg: TransformerConfig, optimizer,
                    n_microbatches: Optional[int] = None):
    """Build (init_sharded_state, jitted train_step) for the mesh.

    train_step(params, opt_state, (tokens, targets)) →
    (params, opt_state, loss).  Gradient reduction over dp is the
    shard_map transpose of the replicated param specs — the compiled
    analog of hvd.DistributedOptimizer.  The step is dispatched under
    the host span `hvd.train.step` (`traced_step`).

    A model with a layer pattern is trained on a `dp` mesh by
    models/pattern.py (`make_pattern_train_step`: each layer recomputed,
    attention through the flash kernel); with routed experts its step
    returns a fourth value, the routing counts a sparse layer.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    if cfg.patterned:
        from .pattern import make_pattern_train_step
        return make_pattern_train_step(mesh, cfg, optimizer)
    if cfg.attn_kind != "softmax":
        raise HorovodTpuError(
            f"make_train_step: attn_kind {cfg.attn_kind!r} is not "
            "trained here (no backward pass through the retention state); "
            "serve it through InferenceServer or transformer_generate")
    axes = {a: mesh.shape.get(a, 1) > 1 for a in mesh.axis_names}
    pp = mesh.shape.get("pp", 1)
    M = n_microbatches or max(1, pp)
    pspecs = transformer_pspecs(cfg, pp)
    data_spec = P(tuple(a for a in ("dp", "ep") if axes.get(a)) or None,
                  "sp" if axes.get("sp") else None)

    def loss_fn(params, tokens, targets):
        body = lambda p, t, y: _loss_shard(p, t, y, cfg, axes, M)
        return shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, data_spec, data_spec),
            out_specs=P(), check_vma=False,
        )(params, tokens, targets)

    def train_step(params, opt_state, batch):
        tokens, targets = batch
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    def shard_state(params, opt_state):
        """Place params/opt_state on the mesh per the sharding rules."""
        params = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
            params, pspecs)
        # Optimizer state: momentum-like leaves mirror the param tree; any
        # leaf whose shape matches a param leaf inherits its spec, scalars
        # replicate.
        flat_params, _ = jax.tree_util.tree_flatten(params)
        flat_specs = jax.tree_util.tree_leaves(pspecs)
        shape_to_spec = {}
        for p, s in zip(flat_params, flat_specs):
            shape_to_spec.setdefault(p.shape, s)

        def place_opt(leaf):
            spec = shape_to_spec.get(getattr(leaf, "shape", None), P())
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        opt_state = jax.tree_util.tree_map(place_opt, opt_state)
        return params, opt_state

    def shard_lm_batch(batch):
        tokens, targets = batch
        sh = NamedSharding(mesh, data_spec)
        return (jax.device_put(tokens, sh), jax.device_put(targets, sh))

    return traced_step(jax.jit(train_step, donate_argnums=(0, 1))), \
        shard_state, shard_lm_batch


def traced_step(jitted):
    """A train step as callers dispatch it: under the host span
    `hvd.train.step` (utils/timeline.span), which brackets the dispatch
    and waits for nothing.  `.lower` is the jitted step's."""
    @functools.wraps(jitted)
    def step(params, opt_state, batch):
        with span("step", "train"):
            return jitted(params, opt_state, batch)
    step.lower = jitted.lower
    return step
