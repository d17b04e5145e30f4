"""KV-cache incremental decoding for the flagship transformer.

The reference is a training framework with no generation path at all;
this module completes the model family for inference: O(1)-per-token
decode against a persistent KV cache, scan-compiled, greedy or
temperature sampling.

Why it pairs with the long-context features (ops/flash_attention.py,
parallel/sequence.py):

  - **GQA/MQA** is primarily a DECODE optimization — the cache holds
    `n_kv_heads` heads, so a 4:1 grouped config carries 1/4 the cache
    bytes per token.  The grouped attention here never materializes
    repeated heads (reshape-grouped einsum, the decode analog of the
    flash kernel's shared-kv index maps).
  - **attn_window** bounds the LIVE span, and the cache is a RING
    BUFFER over absolute positions: with a window, `max_len` may be as
    small as the window itself and decoding continues indefinitely —
    slot `pos % max_len` is overwritten and the band mask works on the
    reconstructed absolute position of each slot.

MoE configs decode with NO-CAPACITY top-1 routing (`_moe_tokens`):
every token reaches its chosen expert — inference has no step-global
token budget, so training's capacity eviction (a load-balancing
device, not a semantic) does not apply.  Decode logits equal the
training forward whenever training's capacity dropped nothing (the
test anchor uses capacity_factor = n_experts).  Expert compute runs
all-experts-then-mask (static shapes; E x the single-token MLP cost,
negligible at decode and acceptable at prefill for modest E).

Layout: cache k/v are HEAD-MAJOR, [L, B, Hkv, max_len, Dh] in
`cfg.compute_dtype` (a quantized cache's scales [L, B, Hkv, max_len]),
`pos` a scalar int32 count of tokens already absorbed.  The one reader
of a layer's keys and values is `_decode_layer`: the kernel of
ops/decode_attention.py, which reads each row's LIVE blocks of slots
out of the stack (one query a row over a plain ring of two blocks or
more), or else the pair of contractions over every slot
(`_attend_view`: a chunk, a quantized layout, a small ring).  Both
want each kv head's slots contiguous.  The v5e
compiler fuses a `dynamic-slice` of the carried stack into a
contraction's operand, or a change of layout, never both: from a
slot-major cache ([L, B, max_len, Hkv, Dh]) every layer's K and V were
first copied out head-major, half of a served decode step (PERF.md,
PR 27 and PR 29).  Held head-major the slice is read where it lies.  The
price is the write's shape: a token's new K is Hkv pieces of Dh numbers
a slot apart and no longer one vector (`_cache_write`,
`_cache_write_rows`); prefill transposes the prompt's K and V once.
`init_decode_cache` defines the layout and `cache_slots` reads the ring
length off it; nobody else names an axis past the rows (axis 1).
All steps are fixed-shape (dynamic_update_slice into the ring; band
masks over the full buffer), so one compiled program serves the whole
generation.
Prefill is ONE batched forward through the training attention path
(`parallel.sequence.full_attention`), not a per-token loop.  The cache
is updated IN PLACE: the layer walk carries the stacked arrays and each
layer writes only the slots it fills (`_layer_walk`); the compiled
entry points that take a cache donated (`_spec_step_fn` and its
siblings, `make_decode_step`) return it in the same buffers.

Power retention (`cfg.attn_kind == "retention"`): key j weighs
`exp(sum_{l=j+1..t} gamma_l) * (q_t . k_j / sqrt(d)) ** 2` for query t,
divided by the sum of the weights plus `RETENTION_EPS`; no softmax.  With
`phi(u)` the symmetric square of u (`_phi`), `phi(q) . phi(k)` is that
weight before decay, so the layer is a recurrence over a FIXED state a
row: per kv head `S = e^gamma S + phi(k) v^T` [D, d_head] and
`z = e^gamma z + phi(k)` [D], read out as `phi(q)^T S / (phi(q)^T z +
eps)`.  The cache is then {"s": [L, B, Hkv, D, Dh], "z": [L, B, Hkv, D],
"pos"} whatever the context length (`init_decode_cache`); a decode step
reads and writes all of it (`_retention_decode_layer`) and prefill is a
scan over chunks of `RETENTION_CHUNK` tokens, quadratic inside a chunk,
the state carried across (`_retention_prefill_layer`).  Both ride
`_layer_walk` under its one contract, the state where keys and values
ride for a softmax layer.  What needs snapshots or shards of a state
(`transformer_extend`, speculation, beam search, `make_decode_step`) and
the quantized layouts refuse the kind by name.

Patterned models (`cfg.layer_attn`: a kind of attention a layer, an MLP
kind a layer): the layers are softmax layers, `_decode_layer` and
`_prefill_layer` themselves, each handed its kind's uniform
configuration (`cfg.kind_cfg(kind)`: that kind's heads, window and
rotary form, `_rotate`; a gate a head, `_head_gate`), over parameters
stacked by KIND of layer and a cache that holds, under each of the two
leaves, one stacked ring a kind (`_empty_pattern`): a kind with a window
keeps `window` slots a row whatever `max_len`, and a prompt longer than
that leaves its last `window` tokens there, each at `pos % window`.
`_pattern_walk` walks the pattern unrolled under `_layer_walk`'s
contract; a layer's MLP is `_mlp_block` or the routed experts of
models/experts.py, whose counts a pass ride in the cache as `routed`.
What extends a ring by a chunk, rolls back, shards or quantizes refuses
such a model by name (`_needs_uniform`).

Latent attention (a kind whose spec is a `LatentSpec`; "Latent
attention" below has the equations): the cache holds ONE normed latent
and ONE rotated key a token and layer, a head of each under the two
leaves, of unequal width ([L, B, 1, slots, kv_rank] and [L, B, 1, slots,
key_lanes]).  The layer has two forms over that one cache: a prompt is
prefilled EXPANDED (`_latent_prefill_layer`: every head's keys and values
made from the prompt's latents, the flash kernel, heads in groups), a
row is stepped ABSORBED (`_latent_decode_layer`: the key's expansion
moved onto the query, ops/decode_attention.py's walk over the live
blocks with every query head against the one head, the value's expansion
after it).  The kind's record is looked up from the kind's own
configuration (`cfg.latent`), so `_pattern_walk` hands each layer what it
hands a softmax layer.  A long prompt of a wide model goes through the
dense MLP and the experts in passes of tokens and its residual stream is
written after every half layer (`_mlp_passes`, `experts.expert_layer`,
`_written`): a 16384-token prefill keeps 2.1 GB of temporaries where it
asked for 6.1.  A chunk at a step, the quantized layouts, speculation,
beam search, sharding and training refuse the kind by name.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common.exceptions import InvalidRequestError
from ..ops import decode_attention, retention_step
from ..parallel import sequence as seq_mod
from . import experts as experts_mod
from .transformer import (
    TransformerConfig,
    _is_moe_layer,
    _mlp_block,
    _rmsnorm,
    _rope,
    refuse_unserved,
)


class _Kind(NamedTuple):
    """What `cfg.attn_kind` decides; the rest of this module is one code."""
    leaves: Tuple[str, str]     # the cache's two stacked leaves, by name
    #: a slot a token?  If not, `max_len` sizes nothing, and what
    #: quantizes, shards, snapshots or rolls back refuses (`_needs_slots`)
    slots: bool
    empty: Callable             # (cfg, batch, max_len, quantize) -> leaves
    step: Callable              # (lp, ca, cb, i, x, pos, cfg, tp_axis)
    prefill: Callable           # (lp, ca, cb, i, x, cfg, tp_axis, chunk)


def _kind(cfg: TransformerConfig) -> _Kind:
    """`cfg`'s record: the one place this module reads `cfg.attn_kind`.
    A new kind is one more entry (its layers under `_layer_walk`'s
    contract) and, to be served, one cache class in serve/pool.py.  Made
    when asked for, so that a layer a test has replaced in this module
    is the one the next program traces."""
    softmax = _Kind(
        ("k", "v"), True, _empty_ring, _decode_layer,
        # a ring is filled in one pass: no chunks
        lambda *a, chunk, **kw: _prefill_layer(*a, **kw))
    if cfg.patterned:
        # each layer is handed its kind's uniform configuration by
        # `_pattern_walk` and is that configuration's own record's (a
        # softmax layer, or a latent one); the cache is a ring a kind.
        # A convolution kind has no record here yet (ROADMAP R2).
        refuse_unserved(cfg, "serving and generation")
        return _Kind(
            ("k", "v"), True, _empty_pattern,
            lambda *a, cfg, **kw: _kind(cfg).step(*a, cfg=cfg, **kw),
            lambda *a, cfg, **kw: _kind(cfg).prefill(*a, cfg=cfg, **kw))
    if cfg.latent is not None:
        # a latent kind's layers (`cfg` is `kind_cfg` of such a kind):
        # leaf one the latent, leaf two the shared rotated key
        return _Kind(
            ("k", "v"), True, _empty_latent, _latent_decode_layer,
            lambda *a, chunk, **kw: _latent_prefill_layer(*a, **kw))
    kinds = {
        "softmax": softmax,
        "retention": _Kind(
            ("s", "z"), False, _empty_state, _retention_decode_layer,
            _retention_prefill_layer),
    }
    if cfg.attn_kind not in kinds:
        raise InvalidRequestError(
            f"attn_kind must be one of {sorted(kinds)}, got "
            f"{cfg.attn_kind!r}")
    return kinds[cfg.attn_kind]


def init_decode_cache(cfg: TransformerConfig, batch: int,
                      max_len: int, quantize=None) -> Dict:
    """Empty KV cache for `batch` sequences.

    `max_len` is the ring capacity: without a window it must cover the
    whole sequence; with `cfg.attn_window` it may be as small as the
    window (the ring then rolls forever).

    `quantize="int8"` (or `"fp8_e4m3"`, the v5e-native float8) stores
    k/v in the 1-byte payload with per-vector f32 scales (max-abs over
    the head dim) — ~1/4 the cache bytes of an f32 compute dtype, the
    decode-side sibling of the int8/fp8 wire compression
    (ops/quantized.py).  The scales factor into the attention
    contractions; writes quantize one vector per step."""
    if batch < 1:
        raise InvalidRequestError(
            f"batch must be >= 1, got {batch} (an empty cache would "
            "fail silently at the first decode step)")
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    # A cache SMALLER than the window is fine as long as the ring never
    # wraps (total tokens <= max_len) — eviction only matters past
    # max_len.  The wrap-capable entry points (transformer_generate /
    # transformer_beam_search via _resolve_max_len) enforce
    # max_len >= attn_window exactly when the sequence will wrap; raw
    # decode_step callers own the contract (see its docstring).
    if quantize not in (None, "int8", "fp8_e4m3"):
        raise ValueError(f"quantize must be None, 'int8', or "
                         f"'fp8_e4m3', got {quantize!r}")
    _needs_slots(cfg, "quantize", quantize is not None)
    _needs_uniform(cfg, "quantize", quantize is not None)
    return {**_kind(cfg).empty(cfg, batch, max_len, quantize),
            "pos": jnp.zeros((), jnp.int32)}


def _empty_ring(cfg, batch, max_len, quantize) -> Dict:
    # Head-major: a layer's slice is what the contractions of
    # `_decode_layer` read, with no copy between (module text).
    shape = (cfg.n_layers, batch, cfg.kv_heads, max_len, cfg.d_head)
    if quantize is not None:
        qdt = jnp.int8 if quantize == "int8" else jnp.float8_e4m3fn
        kv = lambda: {"q": jnp.zeros(shape, qdt),
                      "scale": jnp.zeros(shape[:-1], jnp.float32)}
        return {"k": kv(), "v": kv()}
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def _empty_pattern(cfg, batch, max_len, quantize) -> Dict:
    """A patterned model's cache: under each leaf one stacked ring a KIND
    of attention layer, [L_kind, B, Hkv, slots, Dh] as `_empty_ring` lays
    it.  A kind with a window holds `window` slots a row whatever
    `max_len` (written at `pos % window`, never more), the others
    `max_len`.  `routed` is what the last pass's expert layers counted
    (`experts.ROUTED`), there so that a scan can carry the cache."""
    rings = {t: _kind(kc).empty(kc, batch, kc.attn_window or max_len, None)
             for t in cfg.attn_kinds() for kc in (cfg.kind_cfg(t),)}
    return {"k": {t: r["k"] for t, r in rings.items()},
            "v": {t: r["v"] for t, r in rings.items()},
            "routed": experts_mod.no_counts(cfg)}


def _empty_latent(cfg, batch, max_len, quantize) -> Dict:
    # One head under both leaves, as `_empty_ring` lays it: the normed
    # latent under the first, the rotated key every head shares under the
    # second, in whole tiles of lanes (`LatentSpec.key_lanes`).
    sp, lead = cfg.latent, (cfg.n_layers, batch, 1, max_len)
    return {"k": jnp.zeros(lead + (sp.kv_rank,), cfg.compute_dtype),
            "v": jnp.zeros(lead + (sp.key_lanes,), cfg.compute_dtype)}


def _empty_state(cfg, batch, max_len, quantize) -> Dict:
    # A fixed state a row: `max_len` sizes nothing.
    shape = (cfg.n_layers, batch, cfg.kv_heads,
             retention_features(cfg.d_head))
    return {"s": jnp.zeros(shape + (cfg.d_head,), cfg.state_dtype),
            "z": jnp.zeros(shape, jnp.float32)}


def _quant_vec(x, qdt):
    """Per-vector quantization to `qdt` (int8 or fp8_e4m3): scale =
    max|x| / payload_max over the trailing dim, so the largest element
    lands at the payload's edge and nothing saturates."""
    payload_max = 127.0 if qdt == jnp.int8 else 448.0
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / payload_max,
                        1e-12)
    scaled = xf / scale[..., None]
    q = (jnp.round(scaled) if qdt == jnp.int8 else scaled).astype(qdt)
    return q, scale


def cache_slots(c) -> int:
    """Ring slots a row holds, off a stacked K or V leaf (plain, or the
    {"q", "scale"} dict of a quantized cache; of a patterned model's
    rings a kind, the longest)."""
    if isinstance(c, dict) and "q" not in c:
        return max(cache_slots(a) for a in c.values())
    return (c["q"] if isinstance(c, dict) else c).shape[3]


def _cache_put(c, val, put):
    """Apply `put(array, update)` to a plain cache array or, after
    quantizing `val` per vector, to both leaves of a {"q", "scale"}
    cache (payload and scale take the same indices)."""
    if isinstance(c, dict):
        q, scale = _quant_vec(val, c["q"].dtype)
        return {"q": put(c["q"], q), "scale": put(c["scale"], scale)}
    return put(c, val)


def _cache_write(c, i, val, slot):
    """Write `val` [B, n, Hkv, Dh] (one position at decode, the whole
    prompt at prefill — the slice length comes from val) into layer
    `i` of the STACKED, possibly quantized cache `c` [L, B, Hkv, S, ...],
    starting at ring slot `slot` of every row.  Only those n slots are
    touched: the update lands in the carried array itself, laid
    [B, Hkv, n, ...] as the cache is."""
    return _cache_put(c, val, lambda a, u: lax.dynamic_update_slice(
        a, jnp.swapaxes(u, 1, 2)[None],
        (i, 0, 0, slot) + (0,) * (a.ndim - 4)))


def _cache_write_rows(c, i, val, slots):
    """Per-row variant of `_cache_write`: each batch row writes its
    chunk at its OWN ring slot (`slots` [B] int32) — the vector-pos
    decode path for continuously batched serving, where admitted
    sequences sit at different depths of the same compiled step.
    Writes the same bytes `_cache_write` would per row (quantization is
    per-vector, data movement is exact), so scalar/vector parity is
    bitwise when all rows share a position.

    One scatter a leaf, of B x n x Hkv pieces at (i, row, head,
    slot + j) with Dh the update's window.  The kv heads ride among the
    INDICES: with Hkv in the window beside Dh (B x n updates of a whole
    vector, by `.at[i, rows, :, cols]` or by explicit dimension numbers
    with a [Hkv, 1, Dh] window) the v5e compiler wants the window's
    axes innermost in memory and transposes the whole cache slot-major
    at the program's entry and back at its exit (1.9 GB of temporaries
    at 32 rows x 3584 slots x 8 layers; PERF.md, PR 29), as it does for
    a vmap of `dynamic_update_slice` over the rows (PR 27)."""
    B, n, Hkv = val.shape[:3]
    rows = jnp.arange(B)[:, None, None]
    cols = (slots[:, None] + jnp.arange(n)[None, :])[:, :, None]   # [B,n,1]
    heads = jnp.arange(Hkv)[None, None, :]
    # (layer, row, head, slot) are adjacent indices, so the update is
    # laid [B, n, Hkv, ...]: `val` as it comes.
    return _cache_put(c, val, lambda a, u: a.at[i, rows, heads, cols].set(
        u, indices_are_sorted=True, unique_indices=True))


def _cache_layer(c, i):
    """Layer `i` of a stacked cache, [B, Hkv, S, ...] per leaf, for reading:
    a slice the consumer takes straight out of the carried array."""
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), c)


def _rope_rows(x, positions, theta: float):
    """Rotary embedding with PER-ROW positions: x [B, c, H, Dh],
    positions [B, c] int (`transformer._rope` is the shared-[T]
    variant).  Same elementwise math row by row, so it matches _rope
    bitwise whenever the rows agree."""
    Dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)        # [B, c, Dh/2]
    x1, x2 = x[..., ::2], x[..., 1::2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _rotate(x, positions, cfg: TransformerConfig):
    """The layer's rotary embedding of x [B, c, H, Dh] at `positions`
    ([c], or [B, c] for rows at their own depths).  A plain form
    (`cfg.rotary` None) is `_rope` / `_rope_rows` at `cfg.rope_theta`,
    as ever; otherwise `cfg.rotary` says which share of the head
    rotates, at which frequencies and how scaled (`Rotary`)."""
    if cfg.rotary is None:
        rope = _rope_rows if positions.ndim == 2 else _rope
        return rope(x, positions, cfg.rope_theta)
    freqs, n = cfg.rotary.tables(x.shape[-1])
    angles = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(freqs, jnp.float32)
    if positions.ndim == 1:
        angles = angles[None]                          # [1|B, c, n]
    cos = (jnp.cos(angles) * cfg.rotary.attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * cfg.rotary.attention_factor)[:, :, None, :]
    x1, x2 = x[..., 0:2 * n:2], x[..., 1:2 * n:2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate(
        [out.reshape(x.shape[:-1] + (2 * n,)).astype(x.dtype),
         x[..., 2 * n:]], axis=-1)


def _head_gate(lp, h, o, cfg: TransformerConfig):
    """A patterned model's gate on the attention output: one sigmoid a
    head from the normed hidden vector, o [B, c, H, Dh] float32."""
    g = jnp.einsum("bod,dh->boh", h, lp["w_gate"].astype(cfg.compute_dtype),
                   preferred_element_type=jnp.float32)
    return o * jax.nn.sigmoid(g)[..., None]


def _slot_positions(pos, S):
    """Absolute position held by each ring slot after the write at
    `pos`: slot j holds pos - ((pos - j) mod S); negative = never
    written."""
    j = jnp.arange(S)
    return pos - ((pos - j) % S)


def _attend_view(qg, ck, cv, i, pos, positions, cfg: TransformerConfig):
    """The read side of `_decode_layer` as two contractions over EVERY
    slot of the view: qg [B, c, Hkv, g, Dh] against layer `i` of the
    stacked leaves, masked on each slot's reconstructed absolute
    position.  Returns o [B, c, Hkv, g, Dh] float32.  The path of a
    chunk (c > 1), of the quantized layouts and of a ring of one block
    or less; a plain ring that spans more is read by
    ops/decode_attention.py, live slots only."""
    B, c, Dh = qg.shape[0], qg.shape[1], qg.shape[-1]
    S = cache_slots(ck)
    vec = pos.ndim == 1
    # Grouped attention against the ring: q [B,c,Hkv,g,Dh] x
    # cache [B,Hkv,S,Dh] — the repeated kv heads never materialize, and
    # the layer's slice of the stack is the operand as it lies.
    # Under an int8 cache the per-vector scales FACTOR OUT of the
    # contractions (scale is constant over Dh), so they multiply the
    # [..,S]-shaped scores/probs instead of a Dh-times-larger
    # dequantized cache copy.
    lk, lv = _cache_layer(ck, i), _cache_layer(cv, i)
    if isinstance(lk, dict):
        s = jnp.einsum("bqhgd,bhkd->bhgqk", qg.astype(jnp.float32),
                       lk["q"].astype(jnp.float32))
        s = s * lk["scale"][:, :, None, None, :]
        s = s / (Dh ** 0.5)
    else:
        s = jnp.einsum("bqhgd,bhkd->bhgqk", qg.astype(jnp.float32),
                       lk.astype(jnp.float32)) / (Dh ** 0.5)
    # Per-query causal mask over reconstructed absolute positions:
    # query i (absolute pos+i) sees slots holding abs <= pos+i.  The
    # chunk's own keys were just written, so intra-chunk causality
    # falls out of the same comparison.
    if vec:
        last = pos[:, None] + (c - 1)                    # [B, 1]
        j = jnp.arange(S)[None, :]
        abs_pos = last - ((last - j) % S)                # [B, S]
        q_pos = positions                                # [B, c]
        valid = (abs_pos[:, None, :] >= 0) & \
            (abs_pos[:, None, :] <= q_pos[:, :, None])   # [B, c, S]
        if cfg.attn_window:
            valid = valid & ((q_pos[:, :, None] - abs_pos[:, None, :])
                             < cfg.attn_window)
        s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    else:
        abs_pos = _slot_positions(pos + c - 1, S)        # [S]
        q_pos = positions                                # [c]
        valid = (abs_pos[None, :] >= 0) & \
            (abs_pos[None, :] <= q_pos[:, None])         # [c, S]
        if cfg.attn_window:
            valid = valid & ((q_pos[:, None] - abs_pos[None, :])
                             < cfg.attn_window)
        s = jnp.where(valid[None, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if isinstance(lv, dict):
        pv = p * lv["scale"][:, :, None, None, :]
        o = jnp.einsum("bhgqk,bhkd->bqhgd", pv,
                       lv["q"].astype(jnp.float32))
    else:
        o = jnp.einsum("bhgqk,bhkd->bqhgd", p,
                       lv.astype(jnp.float32))
    return o


def _decode_layer(lp, ck, cv, i, x, pos, cfg: TransformerConfig,
                  tp_axis=None):
    """Layer `i`'s attention for a CHUNK of c new token positions
    (c == 1 is the plain decode step; c > 1 serves `transformer_extend`
    and the speculative verify pass).

    x [B, c, D]; ck/cv [L, B, Hkv, S, Dh], the WHOLE stacked cache
    (LOCAL head counts under tensor parallelism; head dims are derived
    from the weights, not cfg, so tp shards just work); `i` the layer's
    index into it, a traced scalar under the scan or a Python int.
    Returns (x, ck, cv): the same stacked arrays with only slots
    `pos % S .. (pos+c-1) % S` of layer `i` overwritten — B x c vectors
    written in place, nothing else of the cache moved — and attention
    reads layer `i` out of them: shape and type pick the reader, the
    kernel over each row's live blocks or `_attend_view` over every
    slot.  Chunks with c > 1 must not wrap the ring (the c == 1 step
    may).

    `pos` may be a SCALAR (all rows at the same depth — the classic
    batch path) or a [B] VECTOR (each row at its own depth — the
    continuous-batching serving path): rope angles, ring slots, and the
    causal mask are then computed per row.  With equal entries the
    vector path is bitwise-identical to the scalar path (same
    elementwise ops, broadcast vs materialized operands;
    tests/test_decode.py::test_layer_walk_in_place).
    """
    dt = cfg.compute_dtype
    B, S = x.shape[0], cache_slots(ck)
    Dh = cfg.d_head
    c = x.shape[1]

    h = _rmsnorm(lp["ln1"]["scale"], x)
    q = jnp.einsum("bod,dhk->bohk", h, lp["wq"].astype(dt))
    k = jnp.einsum("bod,dhk->bohk", h, lp["wk"].astype(dt))
    v = jnp.einsum("bod,dhk->bohk", h, lp["wv"].astype(dt))
    Hq, Hkv = q.shape[2], k.shape[2]
    g = Hq // Hkv
    pos = jnp.asarray(pos)
    vec = pos.ndim == 1
    if vec:
        positions = pos[:, None] + jnp.arange(c)[None, :]   # [B, c]
        q = _rotate(q, positions, cfg).astype(dt)
        k = _rotate(k, positions, cfg).astype(dt)
        ck = _cache_write_rows(ck, i, k, pos % S)
        cv = _cache_write_rows(cv, i, v, pos % S)
    else:
        positions = pos + jnp.arange(c)                # [c]
        q = _rotate(q, positions, cfg).astype(dt)
        k = _rotate(k, positions, cfg).astype(dt)
        slot = pos % S
        ck = _cache_write(ck, i, k, slot)
        cv = _cache_write(cv, i, v, slot)

    qg = q.reshape(B, c, Hkv, g, Dh)
    if c == 1 and not isinstance(ck, dict) and decode_attention.reads_live(S):
        # one query a row over a plain ring of several blocks: the
        # kernel reads each row's live blocks and no other (a scalar
        # `pos` is a [B] of equal entries)
        o = decode_attention.decode_attention(
            qg[:, 0], ck, cv, i, jnp.broadcast_to(pos, (B,)),
            window=cfg.attn_window)
    else:
        o = _attend_view(qg, ck, cv, i, pos, positions, cfg)
    o = o.reshape(B, c, Hq, Dh)
    if "w_gate" in lp:
        o = _head_gate(lp, h, o, cfg)
    o = o.astype(dt)
    out = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dt))
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)   # row-parallel wo
    x = x + out.astype(x.dtype)
    return x, ck, cv


# -- power retention ----------------------------------------------------------

RETENTION_EPS = 1e-6
# One chunk length for every prompt: on the v5e a 4096-token prefill took
# 0.455 to 0.512 s over chunks of 128 to 1024 (PERF.md, PR 28), so it is
# no option.  The power is 2 by construction: `_phi` is the symmetric
# square and nothing else.
RETENTION_CHUNK = 256


def retention_features(d_head: int) -> int:
    """Numbers `_phi` holds a vector of `d_head` in: d_head / 2 + 1
    wrapped diagonals of d_head, 8320 for 128 (the symmetric square has
    8256 distinct entries; the middle diagonal holds its 64 twice)."""
    return (d_head // 2 + 1) * d_head


def cache_leaves(cfg: TransformerConfig) -> Tuple[str, str]:
    """The cache's two stacked leaves by name: keys and values, or a
    retention model's states and their normalisers."""
    return _kind(cfg).leaves


def _needs_slots(cfg: TransformerConfig, what: str,
                 asked: bool = True) -> None:
    if asked and not _kind(cfg).slots:
        raise InvalidRequestError(
            f"{what} is not supported for attn_kind={cfg.attn_kind!r}: "
            "the cache is one state a row, with no slots to quantize, "
            "shard, snapshot or roll back")


def _needs_uniform(cfg: TransformerConfig, what: str,
                   asked: bool = True) -> None:
    if asked and cfg.latent_kinds():
        raise InvalidRequestError(
            f"{what} is not supported for a model with a latent kind of "
            f"attention layer (a LatentSpec: "
            f"{', '.join(cfg.latent_kinds())}): its cache is one latent "
            "and one shared key a token, written by a whole prompt "
            "(expanded) or by one token a row (absorbed), with nothing yet "
            "to quantize, shard, extend by a chunk or roll back")
    if asked and cfg.patterned:
        raise InvalidRequestError(
            f"{what} is not supported for a model with a layer pattern "
            "(layer_attn): its cache is a ring a kind of layer, window "
            "layers' of `window` slots, with nothing yet to quantize, "
            "shard, extend by a chunk or roll back")


@functools.lru_cache(maxsize=None)
def _phi_tables(d: int):
    """(scale [d/2+1, d], pick [d, (d/2+1) d]) of `_phi`'s layout: the
    weight of each entry, and the 0/1 matrix whose column (o, i) picks
    u_{(i+o) mod d}."""
    n = d // 2 + 1
    scale = np.full((n, d), np.sqrt(2.0 / d), np.float32)
    scale[0] = scale[-1] = np.sqrt(1.0 / d)
    pick = np.zeros((d, n * d), np.float32)
    o, i = np.divmod(np.arange(n * d), d)
    pick[(i + o) % d, np.arange(n * d)] = 1.0
    return scale, pick


def _phi(u):
    """Symmetric square of u [..., d] as [..., (d/2+1) * d] float32, so
    that `_phi(q) . _phi(k) == (q . k) ** 2 / d`.

    Entry (o, i) is `c_o u_i u_{(i+o) mod d} / sqrt(d)` for o = 0..d/2:
    wrapped diagonal o holds the pairs at distance o and at distance
    d - o.  c is 1 on the main diagonal (the squares), sqrt 2 for
    0 < o < d/2 (each pair once), and 1 for o = d/2, whose d entries are
    d/2 pairs held twice.  The same inner products as the d(d+1)/2
    upper-triangle entries, in rows of d lanes with no gather."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    rolled = jnp.stack([jnp.roll(u, -o, axis=-1)
                        for o in range(d // 2 + 1)], axis=-2)
    out = rolled * u[..., None, :] * _phi_tables(d)[0]
    return out.reshape(u.shape[:-1] + (retention_features(d),))


def _retention_qkv(lp, x, positions, cfg: TransformerConfig):
    """What both retention paths start from: q [B, T, H, Dh] and
    k [B, T, Hkv, Dh] normed per head (learned scale) and rotated,
    returned in float32; v in the compute dtype; gamma [B, T, Hkv] = log
    sigmoid of the decay gate, float32, <= 0.  What becomes of q and k
    is the caller's: the decode step keeps them float32 through products
    at "highest", the prefill's matrix products take them in the compute
    dtype as its other products do.  `positions` [T] or, for rows at
    their own depths, [B, T]."""
    dt = cfg.compute_dtype
    h = _rmsnorm(lp["ln1"]["scale"], x)
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dt))
    q = _rmsnorm(lp["q_norm"]["scale"], q.astype(jnp.float32))
    k = _rmsnorm(lp["k_norm"]["scale"], k.astype(jnp.float32))
    rope = _rope_rows if positions.ndim == 2 else _rope
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    gate = jnp.einsum("btd,dh->bth", h, lp["w_decay"].astype(dt),
                      preferred_element_type=jnp.float32)
    gamma = jax.nn.log_sigmoid(gate + lp["b_decay"].astype(jnp.float32))
    return q, k, v, gamma


def _retention_out(lp, x, y, cfg: TransformerConfig, tp_axis=None):
    """Read-out y [B, T, H, Dh] through wo, onto the residual."""
    dt = cfg.compute_dtype
    out = jnp.einsum("bthk,hkd->btd", y.astype(dt), lp["wo"].astype(dt))
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)
    return x + out.astype(x.dtype)


def _state_put(c, i, val):
    """Write layer `i` of a stacked state or normaliser whole, in the
    carried array itself."""
    return lax.dynamic_update_slice(
        c, val[None].astype(c.dtype), (i,) + (0,) * (c.ndim - 1))


def _state_pass(fq, fk, v0, decay, cs, cz, i):
    """Layer `i`'s pass over the state as three einsums, the form
    ops/retention_step.py's kernel is held against and what every shape
    it does not take runs: (num [B,Hkv,g,Dh], den [B,Hkv,g]) read by
    `fq` out of the state AS IT WAS, before the decay, and the stacked
    leaves with layer `i` decayed and given `fk v0^T` / `fk`.  The
    read-out runs at "highest" so that a float32 state is read as
    float32 (a default matmul would round it to bfloat16 on the way
    in)."""
    s = _cache_layer(cs, i).astype(jnp.float32)
    z = _cache_layer(cz, i)
    num = jnp.einsum("bhgf,bhfd->bhgd", fq, s,
                     precision=lax.Precision.HIGHEST)
    den = jnp.einsum("bhgf,bhf->bhg", fq, z,
                     precision=lax.Precision.HIGHEST)
    s = decay[..., None, None] * s + fk[..., None] * v0[..., None, :]
    z = decay[..., None] * z + fk
    return num, den, _state_put(cs, i, s), _state_put(cz, i, z)


def _retention_decode_layer(lp, cs, cz, i, x, pos,
                            cfg: TransformerConfig, tp_axis=None):
    """Layer `i` for ONE new token a row: x [B, 1, D]; cs [L, B, Hkv, Df,
    Dh] and cz [L, B, Hkv, Df] the whole stacked states and normalisers.
    The state is read out by phi(q), decayed, and takes the token's
    phi(k) v^T: a step's cost is the state's bytes, whatever the tokens
    behind it.  Shape and type pick who makes the pass: the kernel of
    ops/retention_step.py, one read and one write of each live row's
    state where it lies, or `_state_pass` over every row.  `pos` scalar
    or [B], as in `_decode_layer`; with a [B] `pos` a row at depth 0 is
    nobody's (a served batch's idle row: a prompt is never empty), and
    the kernel leaves its state as it is and its output 0."""
    B = x.shape[0]
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim == 1 else pos[None]
    q, k, v, gamma = _retention_qkv(lp, x, positions, cfg)
    Hkv, Dh = k.shape[2], k.shape[3]
    decay = jnp.exp(gamma[:, 0])                            # [B, Hkv]
    k0, v0 = k[:, 0], v[:, 0].astype(jnp.float32)
    qg = q[:, 0].reshape(B, Hkv, -1, Dh)                    # [B,Hkv,g,Dh]
    # What came before, out of the state as it was; the token's own
    # weight from q . k itself, so that it is >= 0 exactly (through phi
    # it would round, and an empty state would divide by that).
    fq = _phi(qg)                                           # [B,Hkv,g,Df]
    fk = _phi(k0)                                           # [B, Hkv, Df]
    own = jnp.square(jnp.einsum("bhgd,bhd->bhg", qg, k0,
                                precision=lax.Precision.HIGHEST)) / Dh
    if retention_step.takes(cs):
        num, den, cs, cz = retention_step.retention_step(
            fq, fk, v0, decay, cs, cz, i,
            pos > 0 if pos.ndim == 1 else None)
    else:
        num, den, cs, cz = _state_pass(fq, fk, v0, decay, cs, cz, i)
    num = decay[..., None, None] * num + own[..., None] * v0[:, :, None, :]
    den = decay[..., None] * den + own
    y = num / (den[..., None] + RETENTION_EPS)
    x = _retention_out(lp, x, y.reshape(B, 1, -1, Dh), cfg, tp_axis)
    return x, cs, cz


def _phi_rows(u, mxu):
    """`_phi` for the many vectors of a prefill chunk.  With `mxu`
    bfloat16 the rolled copies of u come out of a matrix product with a
    0/1 matrix, u split into two bfloat16 halves so that the copies are
    u to 2^-17: on the v5e the lane rotations of `_phi` were a third of
    a prefill and the MXU has room (PERF.md, PR 28).  Float32 models
    take `_phi` itself."""
    if mxu != jnp.bfloat16:
        return _phi(u)
    u = u.astype(jnp.float32)
    scale, pick = _phi_tables(u.shape[-1])
    hi = u.astype(mxu)
    lo = (u - hi.astype(jnp.float32)).astype(mxu)
    rolled = jnp.einsum("...d,df->...f", jnp.concatenate([hi, lo], -1),
                        jnp.asarray(np.concatenate([pick, pick]), mxu),
                        preferred_element_type=jnp.float32)
    return rolled * jnp.tile(u, scale.shape[0]) * scale.reshape(-1)


def _retention_prefill_layer(lp, cs, cz, i, x, cfg: TransformerConfig,
                             tp_axis=None, chunk: int = RETENTION_CHUNK):
    """Layer `i` over a whole prompt x [B, T0, D], from an empty state:
    a scan over chunks of `chunk` tokens.  Inside a chunk the attention
    form (every weight (q.k)^2 / d times its decay, so >= 0 exactly);
    what came before the chunk is read out of the carried
    state by phi(q), and the state then takes the chunk's keys and
    values with the decay each has left at the chunk's end.  The scan
    carries the state and its normaliser as ONE array, [B, Hkv, Df,
    2 Dh]: the values get a column of ones beside them, so the column
    of the state beside Dh sums phi(k) and one matrix product reads out
    numerator and denominator, another updates both.  Every product
    here takes its operands in the compute dtype and adds up in float32,
    as the model's other products do; the carried state itself stays
    float32 from chunk to chunk, so a rounding falls on what one product
    reads and never on what is kept.  A last chunk that the prompt does not fill is padded with tokens that
    neither decay the state nor add to it.  The final state is written
    whole into layer `i` of cs / cz; nothing of them is read."""
    B, T0, _ = x.shape
    C = min(chunk, T0)
    N = -(-T0 // C)
    q, k, v, gamma = _retention_qkv(lp, x, jnp.arange(T0), cfg)
    H, Hkv, Dh = q.shape[2], k.shape[2], k.shape[3]
    g = H // Hkv
    mxu = (jnp.bfloat16 if cfg.compute_dtype == jnp.bfloat16
           else jnp.float32)
    ones = jnp.zeros(v.shape[:-1] + (Dh,), jnp.float32).at[..., 0].set(1.0)
    va = jnp.concatenate([v.astype(jnp.float32), ones], axis=-1)
    pad = N * C - T0
    if pad:
        q, k, va, gamma = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                   * (a.ndim - 2))
                           for a in (q, k, va, gamma))
    # [N, B, C, ...]: the scan walks the chunks
    chunks = lambda a: jnp.moveaxis(
        a.reshape((B, N, C) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((C, C), bool))
    f32 = dict(preferred_element_type=jnp.float32)

    def chunk(sa, inp):                       # sa [B, Hkv, Df, 2 Dh]
        qc, kc, vc, gc = inp
        qg = qc.reshape(B, C, Hkv, g, Dh)
        cum = jnp.cumsum(gc, axis=1)                     # [B, C, Hkv]
        cum_h = cum.transpose(0, 2, 1)                   # [B, Hkv, C]
        # inside the chunk
        w = jnp.einsum("bthgd,bshd->bhgts", qg, kc) / (Dh ** 0.5)
        left = jnp.where(causal, cum_h[..., :, None] - cum_h[..., None, :],
                         -jnp.inf)                       # [B,Hkv,C,C]
        a = jnp.square(w) * jnp.exp(left)[:, :, None]
        ya = jnp.einsum("bhgts,bshe->bthge", a, vc)
        # what came before it
        fq = _phi_rows(qg, mxu).astype(mxu)              # [B,C,Hkv,g,Df]
        ya += jnp.exp(cum)[..., None, None] * jnp.einsum(
            "bthgf,bhfe->bthge", fq, sa.astype(mxu), **f32)
        y = ya[..., :Dh] / (ya[..., Dh:Dh + 1] + RETENTION_EPS)
        # the state at the chunk's end
        kept = jnp.exp(cum_h[..., -1])                   # [B, Hkv]
        fk = _phi_rows(kc, mxu) * jnp.exp(cum[:, -1:] - cum)[..., None]
        sa = kept[..., None, None] * sa + jnp.einsum(
            "bshf,bshe->bhfe", fk.astype(mxu), vc.astype(mxu), **f32)
        return sa, y.reshape(B, C, H, Dh)

    sa, y = lax.scan(
        chunk, jnp.zeros((B, Hkv, retention_features(Dh), 2 * Dh),
                         jnp.float32),
        tuple(chunks(a) for a in (q, k, va, gamma)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, N * C, H, Dh)[:, :T0]
    x = _retention_out(lp, x, y, cfg, tp_axis)
    return (x, _state_put(cs, i, sa[..., :Dh]),
            _state_put(cz, i, sa[..., Dh]))


# -- latent attention --------------------------------------------------------
# A latent kind's layer (`LatentSpec`, `cfg.latent`; multi-head latent
# attention as `deepseek_v3` publishes it).  Per token u = norm1(x) at
# position p: c_q = norm(u W_qa); a head's query (q^n [nope], q^r [rope]) =
# c_q W_qb, q^r rotated; (c', k') = u W_kva, c = norm(c'), r = rope(k', p).
# c [kv_rank] and r [rope_dim] are ALL the cache holds of the token, one
# head under each of the two leaves, for every query head.  A head's key is
# (c W_uk [nope], r) and its value c W_uv [v_dim], (W_uk, W_uv) = W_kvb's
# two parts; softmax over `sp.softmax_scale` times q . k.  Two forms over
# the one cache, the same numbers up to rounding:
#   EXPANDED (a prompt, `_latent_prefill_layer`): every head's keys and
#     values are made from c and the prompt attends to them through the
#     flash kernel, one kv head a query head;
#   ABSORBED (a step, `_latent_decode_layer`): W_uk goes onto the query,
#     qc = q^n W_uk^T [kv_rank], the scores are s (qc . c_j + q^r . r_j),
#     the values ARE the latents, z = sum_j p_j c_j, and W_uv comes after,
#     o = z W_uv: no key or value of a head is ever written, and the read
#     is ops/decode_attention.py's walk over the live blocks with all the
#     query heads against the one head.
# One token a row at a step (a chunk refuses by name), no window, no tp.


def _latent_low(lp, x, positions, cfg: TransformerConfig):
    """What both forms start from, for x [B, c, D] at `positions`: (c_q
    [B, c, q_rank] normed, c [B, c, 1, kv_rank] normed, r [B, c, 1, rope]
    rotated), in the compute dtype; `_lanes` gives r as the cache holds
    it."""
    dt, sp = cfg.compute_dtype, cfg.latent
    h = _rmsnorm(lp["ln1"]["scale"], x)
    cq = _rmsnorm(lp["q_norm"]["scale"],
                  jnp.einsum("btd,dr->btr", h, lp["wq_a"].astype(dt)))
    kv = jnp.einsum("btd,dr->btr", h, lp["wkv_a"].astype(dt))
    c = _rmsnorm(lp["kv_norm"]["scale"], kv[..., :sp.kv_rank])[:, :, None]
    r = _rotate(kv[:, :, None, sp.kv_rank:], positions, cfg).astype(dt)
    return cq, c, r


def _latent_queries(cq, wq_b, positions, cfg: TransformerConfig):
    """The heads of `wq_b` [q_rank, h, nope + rope]: (q^n [B, c, h, nope],
    q^r [B, c, h, rope] rotated)."""
    dt, sp = cfg.compute_dtype, cfg.latent
    q = jnp.einsum("btr,rhk->bthk", cq, wq_b.astype(dt))
    return (q[..., :sp.nope_dim],
            _rotate(q[..., sp.nope_dim:], positions, cfg).astype(dt))


def _lanes(a, sp):
    """a [..., rope_dim] with zeros behind it up to `sp.key_lanes`."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                   + ((0, sp.key_lanes - a.shape[-1]),))


def _latent_out(lp, x, o, cfg: TransformerConfig):
    """Heads' outputs o [B, c, H, v_dim] through wo, onto the residual."""
    dt = cfg.compute_dtype
    out = jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"].astype(dt))
    return x + out.astype(x.dtype)


def _latent_attend_view(qc, qr, ck, cv, i, pos, scale):
    """The absorbed read as contractions over EVERY slot of the view: qc
    [B, H, kv_rank] and qr [B, H, rope] against layer `i` of the stacked
    leaves, masked on each slot's reconstructed absolute position (`pos`
    [B]); z [B, H, kv_rank] float32.  The path of a ring the kernel does
    not take (`decode_attention.reads_live`)."""
    lc = _cache_layer(ck, i)[:, 0].astype(jnp.float32)       # [B, S, R]
    lr = _cache_layer(cv, i)[:, 0, :, :qr.shape[-1]].astype(jnp.float32)
    S = lc.shape[1]
    s = (jnp.einsum("bhr,bsr->bhs", qc.astype(jnp.float32), lc)
         + jnp.einsum("bhk,bsk->bhs", qr.astype(jnp.float32), lr)) * scale
    held = pos[:, None] - ((pos[:, None] - jnp.arange(S)[None, :]) % S)
    p = jax.nn.softmax(jnp.where((held >= 0)[:, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, lc)


def _refuse_latent_tp(tp_axis) -> None:
    if tp_axis is not None:
        raise InvalidRequestError(
            "tensor parallelism is not supported for a latent kind of "
            "attention layer (a LatentSpec): its heads share one latent a "
            "token, which a split of the heads would have to copy")


def _latent_decode_layer(lp, ck, cv, i, x, pos, cfg: TransformerConfig,
                         tp_axis=None):
    """Layer `i` of a latent kind for ONE new token a row, ABSORBED: x
    [B, 1, D]; ck [L, B, 1, S, kv_rank] and cv [L, B, 1, S, key_lanes]
    the whole stacked leaves, updated in place at `pos % S` under
    `_layer_walk`'s contract; `pos` scalar or [B], as in
    `_decode_layer`."""
    _refuse_latent_tp(tp_axis)
    B, c = x.shape[:2]
    if c != 1:
        raise InvalidRequestError(
            f"a latent kind of attention layer (a LatentSpec) is stepped "
            f"one token a row, got a chunk of {c}: the absorbed form has "
            "no mask inside a chunk")
    dt, sp = cfg.compute_dtype, cfg.latent
    S = cache_slots(ck)
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim == 1 else pos[None]
    cq, lat, r = _latent_low(lp, x, positions, cfg)
    qn, qr = _latent_queries(cq, lp["wq_b"], positions, cfg)
    write = _cache_write_rows if pos.ndim == 1 else _cache_write
    ck = write(ck, i, lat, pos % S)
    cv = write(cv, i, _lanes(r, sp), pos % S)
    wkv = lp["wkv_b"].astype(dt)                       # [R, H, nope + v]
    with jax.named_scope("hvd.attn.latent"):
        qc = jnp.einsum("bhk,rhk->bhr", qn[:, 0], wkv[..., :sp.nope_dim])
        rows = jnp.broadcast_to(pos, (B,))
        if decode_attention.reads_live(S):
            z = decode_attention.decode_attention(
                jnp.concatenate([qc.astype(dt), _lanes(qr[:, 0], sp)],
                                -1)[:, None],
                ck, cv, i, rows, scale=sp.softmax_scale, latent=True)[:, 0]
        else:
            z = _latent_attend_view(qc, qr[:, 0], ck, cv, i, rows,
                                    sp.softmax_scale)
        o = jnp.einsum("bhr,rhv->bhv", z.astype(dt), wkv[..., sp.nope_dim:])
    return _latent_out(lp, x, o[:, None], cfg), ck, cv


#: Numbers one head group's q, k or v of a prompt may hold
#: (`_latent_prefill_layer`): 8 of 64 heads at 16384 tokens of width 192,
#: 32 at 4096, every head up to 2048.
_PROMPT_HEAD_NUMBERS = 2 ** 25


def _latent_prefill_layer(lp, ck, cv, i, x, cfg: TransformerConfig,
                          tp_axis=None):
    """Layer `i` of a latent kind over a whole prompt x [B, T0, D],
    EXPANDED: slots 0..T0-1 of both leaves are written in place (the
    latents and the shared keys, nothing a head) and nothing of the cache
    is read; every head's keys and values are made from the prompt's own
    latents and attended to causally, through the flash kernel from 128
    tokens on.  The kernel scales by 1/sqrt of the head width it is
    handed and takes one width for q, k and v: the narrower of key and
    value is padded with zeros to the wider (no score and no output
    changes; at 128 + 64 and 192 nothing is) and the layer's own scale
    goes onto q.  A long prompt's heads go in groups, one after another,
    each expanded from the latents when its turn comes: all 64 heads' q,
    k and v of 16384 tokens would be 1.2 GB beside the same again
    transposed for the kernel."""
    _refuse_latent_tp(tp_axis)
    dt, sp = cfg.compute_dtype, cfg.latent
    B, T0, H = x.shape[0], x.shape[1], sp.n_heads
    positions = jnp.arange(T0)
    cq, lat, r = _latent_low(lp, x, positions, cfg)
    ck = _cache_write(ck, i, lat, 0)
    cv = _cache_write(cv, i, _lanes(r, sp), 0)
    wide = max(sp.nope_dim + sp.rope_dim, sp.v_dim)

    def heads(ws):                  # one group's (wq_b, wkv_b)
        qn, qr = _latent_queries(cq, ws[0], positions, cfg)
        kv = jnp.einsum("btr,rhk->bthk", lat[:, :, 0], ws[1].astype(dt))
        q = jnp.concatenate([qn, qr], -1)
        k = jnp.concatenate(
            [kv[..., :sp.nope_dim],
             jnp.broadcast_to(r, q.shape[:3] + (sp.rope_dim,))], -1)
        v = kv[..., sp.nope_dim:]
        if T0 >= 128:
            q = (q.astype(jnp.float32)
                 * (sp.softmax_scale * wide ** 0.5)).astype(dt)
            padded = lambda a: jnp.pad(
                a, ((0, 0),) * 3 + ((0, wide - a.shape[-1]),))
            return _flash_prompt(padded(q), padded(k), padded(v),
                                 None)[..., :sp.v_dim]
        s = jnp.einsum("bthk,bshk->bhts", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * sp.softmax_scale
        p = jax.nn.softmax(jnp.where(
            jnp.tril(jnp.ones((T0, T0), bool)), s, -1e30), axis=-1)
        return jnp.einsum("bhts,bshk->bthk", p,
                          v.astype(jnp.float32)).astype(dt)

    with jax.named_scope("hvd.attn.latent"):
        hg = max(h for h in range(1, H + 1) if H % h == 0
                 and h <= max(1, _PROMPT_HEAD_NUMBERS // (B * T0 * wide)))
        if hg == H:
            o = heads((lp["wq_b"], lp["wkv_b"]))
        else:
            group = lambda w: jnp.moveaxis(
                w.reshape(w.shape[0], H // hg, hg, w.shape[-1]), 1, 0)
            o = lax.map(heads, (group(lp["wq_b"]), group(lp["wkv_b"])))
            o = jnp.moveaxis(o, 0, 2).reshape(B, T0, H, sp.v_dim)
    return _latent_out(lp, x, o, cfg), ck, cv


def _moe_tokens(mp, scale, x, cfg: TransformerConfig):
    """No-capacity top-1 MoE for decode/prefill: x [B, T, D] ->
    residual-added output.  All experts run on all tokens and the
    result is masked by the routing one-hot (static shapes)."""
    dt = cfg.compute_dtype
    B, T, D = x.shape
    from ..parallel.moe import top1_route

    h = _rmsnorm(scale, x).reshape(B * T, D).astype(dt)
    logits = h @ mp["gate"]["kernel"].astype(dt)            # [N, E]
    _, eidx, gate = top1_route(logits)
    he = jax.nn.relu(jnp.einsum("nd,edf->enf", h,
                                mp["wi"].astype(dt)))       # [E, N, F]
    oe = jnp.einsum("enf,efd->end", he, mp["wo"].astype(dt))
    onehot = jax.nn.one_hot(eidx, oe.shape[0], dtype=jnp.float32)
    out = jnp.einsum("ne,end->nd", onehot * gate[:, None],
                     oe.astype(jnp.float32))
    return x + out.reshape(B, T, D).astype(x.dtype)


#: Numbers one pass of a patterned model's dense MLP may hold in its
#: widened form (`_mlp_passes`): 8192 tokens at a width of 8192, 2048 at
#: 18432.
_MLP_PASS_NUMBERS = 2 ** 26


def _mlp_passes(mp, x, cfg: TransformerConfig):
    """`_mlp_block` on x [B, T, D], a long prompt of a wide model in
    passes of tokens, one after another: 16384 tokens widened to 18432
    are 604 MB three times over."""
    B, T, D = x.shape
    step = 1 << max((_MLP_PASS_NUMBERS // cfg.d_ff).bit_length() - 1, 0)
    if B * T <= step:
        return _mlp_block(mp, x, cfg, None)
    rows = x.reshape(B * T, D)
    rows = jnp.pad(rows, ((0, -(B * T) % step), (0, 0)))
    out = lax.map(lambda r: _mlp_block(mp, r[None], cfg, None)[0],
                  rows.reshape(-1, step, D))
    return out.reshape(-1, D)[:B * T].reshape(B, T, D)


#: From this many bytes on a patterned model's residual stream is WRITTEN
#: after every half layer (`_written`).
_RESIDUAL_BYTES = 2 ** 26


def _written(x):
    """x as an array of its own where it is large.  Left to itself the
    compiler keeps every half layer's addend of the residual stream and
    sums them again inside each reader: at 16384 tokens of width 7168
    ten buffers of 235 MB and the attention outputs behind them, 3 GB of
    a prefill's temporaries."""
    if x.size * x.dtype.itemsize < _RESIDUAL_BYTES:
        return x
    return lax.optimization_barrier(x)


def _pattern_walk(params, ck, cv, x, attn_fn, cfg, live, routed):
    """`_layer_walk` over a layer pattern: the layers unrolled, layer l
    taking the j-th slice of its kinds' stacks (`params["attn"][t]`,
    `params["mlp"][m]`; static indices, so a slice is read where it
    lies) and the j-th layer of its kind's ring, `ck[t]` / `cv[t]`,
    under the same in-place contract.  `attn_fn` is handed the kind's
    uniform configuration in `cfg`'s place.  An expert layer's counts
    are appended to `routed`; `live` [B] marks the rows that are
    anybody's (None: all)."""
    dt = cfg.compute_dtype
    ck, cv = dict(ck), dict(cv)
    seen = {}
    B, T, D = x.shape
    tokens_live = None if live is None else jnp.repeat(live, T)
    for t, m in zip(cfg.layer_attn, cfg.layer_mlp):
        j, jm = seen.get(t, 0), seen.get(m, 0)
        seen[t], seen[m] = j + 1, jm + 1
        lp = jax.tree_util.tree_map(lambda p: p[j], params["attn"][t])
        x, ck[t], cv[t] = attn_fn(lp, ck[t], cv[t], j, x,
                                  cfg=cfg.kind_cfg(t))
        x = _written(x)
        # the experts' stack is not sliced: `experts._grouped` has why
        mp = jax.tree_util.tree_map(
            lambda p: p[jm], {n: p for n, p in params["mlp"][m].items()
                              if n != "experts"})
        if m == "dense":
            x = _written(_mlp_passes(mp, x, cfg))
            continue
        h = _rmsnorm(mp["ln2"]["scale"], x).reshape(B * T, D).astype(dt)
        out, counts = experts_mod.expert_layer(
            mp, params["mlp"][m]["experts"], jm, h, cfg, tokens_live)
        x = _written(x + out.reshape(B, T, D).astype(x.dtype))
        routed.append(counts)
    return x, ck, cv


def _layer_walk(params, ck, cv, x, attn_fn, cfg, tp_axis=None,
                live=None, routed=None):
    """Layer walk shared by decode, chunked extend and prefill, ONE
    contract for every caller: `ck`/`cv` are the whole stacked cache
    (arrays, or the {"q", "scale"} dicts of the quantized layouts) and
    are UPDATED IN PLACE — attn_fn(lp, ck, cv, i, x) -> (x, ck, cv)
    writes only the slots layer `i` fills and reads layer `i` out of
    the same arrays (`_decode_layer`, `_prefill_layer`).

    Homogeneous dense configs scan over (layer index, stacked params)
    with the cache in the scan's CARRY, never among its xs / ys: a
    cache scanned as xs -> ys is cut out and written back a whole
    layer at a time, which was two thirds of a served decode step
    (PERF.md, PR 27).  Mixed dense/MoE configs walk the layers
    unrolled, with static indices and the same contract.  A caller
    that donates the cache to its jit (the server's programs do) gets
    the result in the argument's own buffer.  A patterned model walks
    its pattern (`_pattern_walk`, which alone reads `live` and fills
    `routed`)."""
    if cfg.patterned:
        return _pattern_walk(params, ck, cv, x, attn_fn, cfg, live,
                             [] if routed is None else routed)
    if not cfg.moe_every:
        def layer_step(carry, inputs):
            x, ck, cv = carry
            i, lp = inputs
            x, ck, cv = attn_fn(lp, ck, cv, i, x)
            return (_mlp_block(lp, x, cfg, tp_axis), ck, cv), None

        (x, ck, cv), _ = lax.scan(
            layer_step, (x, ck, cv),
            (jnp.arange(cfg.n_layers), params["blocks"]))
        return x, ck, cv
    moe_idx = 0
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda p: p[i], params["blocks"])
        x, ck, cv = attn_fn(lp, ck, cv, i, x)
        if _is_moe_layer(cfg, i):
            mp = jax.tree_util.tree_map(lambda p: p[moe_idx],
                                        params["moe"])
            # No-capacity routing in decode AND prefill (see module
            # docstring) — the two paths stay self-consistent.
            # MoE weights are replicated over tp (pspecs shard them
            # over ep only), so the routed output is tp-consistent.
            x = _moe_tokens(mp, lp["ln2"]["scale"], x, cfg)
            moe_idx += 1
        else:
            x = _mlp_block(lp, x, cfg, tp_axis)
    return x, ck, cv


def transformer_decode_step(params: Dict, cache: Dict, tokens,
                            cfg: TransformerConfig, tp_axis=None):
    """Absorb one token per sequence; return (logits [B, V], cache).

    `tokens` [B] int32.  The cache is a ring: with `cfg.attn_window`
    set and max_len >= the window, decoding may continue past `max_len`
    indefinitely; without a window — or with a cache smaller than the
    window — the caller must keep the TOTAL sequence within `max_len`
    (older positions would be silently evicted otherwise; the
    generate/beam entry points enforce this via _resolve_max_len).

    `cache["pos"]` may be a [B] VECTOR (per-row depths — the serving
    pool's continuous-batching view, see horovod_tpu/serve/pool.py);
    the step then ropes/writes/masks per row and advances every entry
    by one.
    """
    dt = cfg.compute_dtype
    x = params["embed"][tokens].astype(dt)[:, None, :]    # [B,1,D]
    pos = cache["pos"]
    kind = _kind(cfg)
    ka, kb = kind.leaves

    # A served batch's idle rows sit at depth 0, where no sequence is (a
    # prompt is never empty): a patterned model routes them nowhere.
    live = pos > 0 if jnp.ndim(pos) == 1 else None
    routed = []
    x, ck, cv = _layer_walk(
        params, cache[ka], cache[kb], x,
        functools.partial(kind.step, pos=pos, cfg=cfg, tp_axis=tp_axis),
        cfg, tp_axis, live, routed)
    x = _rmsnorm(params["final_norm"]["scale"], x)
    logits = jnp.einsum("bod,vd->bov", x.astype(dt),
                        params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    return logits[:, 0], {ka: ck, kb: cv, "pos": pos + 1,
                          **_routed(cfg, routed)}


def _routed(cfg: TransformerConfig, routed) -> Dict:
    """What a patterned model's cache says of the pass that made it:
    `routed` [sparse layers, len(experts.ROUTED)]; nothing otherwise."""
    if not cfg.patterned:
        return {}
    return {"routed": jnp.stack(routed) if routed
            else experts_mod.no_counts(cfg)}


def transformer_extend(params: Dict, cache: Dict, tokens,
                       cfg: TransformerConfig, tp_axis=None):
    """Absorb a CHUNK of c tokens [B, c] at cache position pos; return
    (logits [B, c, V], cache) — the per-position next-token logits the
    speculative verify pass needs (reference: none; standard
    draft-verify decoding a la speculative sampling).

    The chunk must fit without wrapping the ring: pos % max_len + c <=
    max_len (enforced eagerly when pos is concrete).  c == 1 is
    numerically identical to `transformer_decode_step`.

    Windowed configs (`cfg.attn_window`) additionally require pos <
    max_len: once the ring has wrapped, the chunk's slot-position
    reconstruction anchors at its LAST query, so keys that are still
    inside an EARLIER query's window may already have been evicted —
    the earlier rows would silently attend over a truncated window.
    Use `transformer_decode_step` past max_len instead (its single
    query is exactly the anchor, so no such skew exists).
    """
    _needs_slots(cfg, "transformer_extend (a chunk of tokens over "
                      "a cache; speculative verify)")
    _needs_uniform(cfg, "transformer_extend (a chunk of tokens over a "
                        "cache; speculative verify)")
    dt = cfg.compute_dtype
    B, c = tokens.shape
    S = cache_slots(cache["k"])
    pos = cache["pos"]
    if not isinstance(pos, jax.core.Tracer):
        # Vector pos (per-row serving depths): every row must fit — the
        # wrap guard checks the worst slot, the window guard the
        # deepest row.
        pos_np = np.asarray(pos).reshape(-1)
        pmax = int(pos_np.max())
        if int((pos_np % S).max()) + c > S:
            raise ValueError(
                f"extend chunk of {c} tokens at pos {pmax} would "
                f"wrap the ring (max_len {S}); split the chunk or size "
                f"the cache larger")
        if cfg.attn_window and pmax >= S:
            raise ValueError(
                f"extend on a wrapped windowed ring (attn_window "
                f"{cfg.attn_window}, pos {pmax} >= max_len {S}) "
                "would silently drop still-in-window keys for the "
                "chunk's earlier queries; decode token-by-token with "
                "transformer_decode_step past max_len")
    x = params["embed"][tokens].astype(dt)                # [B,c,D]
    x, ck, cv = _layer_walk(
        params, cache["k"], cache["v"], x,
        functools.partial(_decode_layer, pos=pos, cfg=cfg,
                          tp_axis=tp_axis),
        cfg, tp_axis)
    x = _rmsnorm(params["final_norm"]["scale"], x)
    logits = jnp.einsum("bod,vd->bov", x.astype(dt),
                        params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    return logits, {"k": ck, "v": cv, "pos": pos + c}


def transformer_speculative_generate(
        params: Dict, cfg: TransformerConfig,
        draft_params: Dict, draft_cfg: TransformerConfig,
        prompt, max_new_tokens: int, gamma: int = 4,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        max_len: Optional[int] = None):
    """Speculative decoding: a small DRAFT model proposes `gamma` tokens
    per round, the TARGET model scores them all in ONE chunked forward
    (`transformer_extend`), and the longest valid prefix is accepted.

    - temperature == 0 (greedy): accept while the draft token equals the
      target argmax; the first mismatch position is replaced by the
      target's own argmax.  Under matched precision the output is the
      target-only greedy sequence token for token; when two logits are
      within numerical noise of each other (near-ties, especially under
      bf16 compute), the chunked verify pass and the step-by-step chain
      may break the tie differently — equivalence then holds up to
      those near-tie positions (tested with a tolerance-aware argmax
      comparison).
    - temperature > 0: standard speculative SAMPLING (Leviathan et al. /
      Chen et al.): draft token x accepted with probability
      min(1, p_target(x)/p_draft(x)); on first rejection, resample from
      norm(max(0, p - q)).  The output distribution equals target-only
      sampling.

    Batching (B > 1) uses MIN-ACCEPTANCE: every round all sequences
    advance by the batch-minimum accepted length + 1, so the shared
    cache position stays scalar.  Per-row VALUES are unaffected — a row
    that accepted beyond the minimum takes its own (already-verified)
    draft token as the round's extra — only throughput degrades toward
    the slowest row (the standard batched-speculation tradeoff).
    Returns (tokens [B, max_new_tokens], stats dict with `rounds`,
    `accept_rate` — the min-based effective rate).  The round loop runs
    in Python; the model passes per round are the compiled pieces
    (draft scan + target chunk extend + one step), so wall-clock per
    round is one draft scan of gamma steps + ONE chunked target
    dispatch — the latency win when the target is dispatch- or
    memory-bound.

    Both models must share the vocabulary; `cfg.attn_window` is not
    supported (rollback across a rolling ring would evict live slots).
    """
    B, T0 = prompt.shape
    for c in (cfg, draft_cfg):
        _needs_slots(c, "speculative decoding (rolling back a round "
                        "needs snapshots of the state)")
        _needs_uniform(c, "speculative decoding")
    if cfg.attn_window or draft_cfg.attn_window:
        raise ValueError(
            "speculative decoding does not support attn_window configs")
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: {draft_cfg.vocab_size} vs "
            f"{cfg.vocab_size}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    # +gamma headroom: a round may write gamma speculative slots past
    # the final accepted position before rolling back.  The rollback
    # machinery assumes the ring never wraps, so an undersized explicit
    # max_len must be rejected here — inside jit the extend wrap guard
    # cannot fire, and dynamic_update_slice would silently CLAMP the
    # write over live slots.
    need = T0 + max_new_tokens + gamma + 1
    cap = max_len or need
    if cap < need:
        raise ValueError(
            f"max_len {cap} < prompt {T0} + max_new {max_new_tokens} + "
            f"gamma {gamma} + 1: speculative rounds write up to gamma "
            f"slots past the accepted frontier before rolling back")
    cache = init_decode_cache(cfg, B, cap)
    dcache = init_decode_cache(draft_cfg, B, cap)

    # Loop invariant (restored at the end of every round): every
    # DECIDED token is fed into both caches, and tlast/dlast are the
    # [B, V] logits (numpy, host) for the next undecided position.
    # Prefill establishes it for the prompt.
    tlast, cache = transformer_prefill(params, cache, prompt, cfg)
    dlast, dcache = transformer_prefill(draft_params, dcache, prompt,
                                        draft_cfg)
    tlast, dlast = np.asarray(tlast), np.asarray(dlast)

    # Compiled programs are module-cached per (cfg, ...) with params as
    # TRACED ARGUMENTS — repeat calls with the same configs reuse the
    # executables and the weights are not baked in as constants.
    extend = _spec_extend_fn(cfg)
    tstep = _spec_step_fn(cfg)
    dstep = _spec_step_fn(draft_cfg)

    def _at(c, pos):
        return {"k": c["k"], "v": c["v"],
                "pos": jnp.asarray(pos, jnp.int32)}

    # Single-use key discipline: one branch seeds the host
    # accept/resample stream, the other drives the draft-sampling keys.
    host_key = None
    if rng is not None:
        rng, host_key = jax.random.split(rng)
    rng_np = np.random.default_rng(
        int(jax.random.randint(host_key, (), 0, 2**31 - 1))
        if host_key is not None else 0)

    def _host_pick(logits_np):
        if not temperature:
            return int(np.argmax(logits_np))
        p = _softmax_np(logits_np / temperature)
        return int(rng_np.choice(len(p), p=p))

    out = [[] for _ in range(B)]    # decided tokens per row
    rounds = 0
    accepted_total = 0
    proposed_total = 0
    base = T0                       # first undecided position (host)
    while len(out[0]) < max_new_tokens:
        rounds += 1
        # Always propose a full gamma chunk — a shorter final round
        # would compile a SECOND (dscan, extend) shape pair just to
        # absorb the tail; the cache reserves gamma headroom past the
        # frontier and the final truncation discards any surplus.
        n = gamma
        # --- draft proposes n tokens per row in ONE compiled scan ---
        # qlogits[i] is the distribution row b's d_i was drawn from;
        # the scan feeds every drafted token (the rollback below erases
        # the speculative tail either way).
        keys = (jax.random.split(rng, n + 1) if rng is not None
                else jnp.zeros((n + 1, 2), jnp.uint32))
        rng = keys[0] if rng is not None else None
        dscan = _spec_draft_scan(draft_cfg, n, bool(temperature))
        if temperature:
            drafts_d, qlogits_d, dcache = dscan(
                draft_params, dcache, jnp.asarray(dlast), keys[1:],
                jnp.float32(temperature))
            qlogits = np.asarray(qlogits_d)        # [n, B, V]
        else:
            drafts_d, dcache = dscan(
                draft_params, dcache, jnp.asarray(dlast), keys[1:],
                jnp.float32(1.0))
            qlogits = None
        drafts = np.asarray(drafts_d)              # [n, B] int
        proposed_total += n
        # --- target scores all n in ONE chunked forward -------------
        # Row i predicts position base+1+i; position base is judged by
        # tlast, so each row's target distributions are [tlast[b],
        # tlogits[b, 0..n-2]] and tlogits[b, n-1] supplies the
        # all-accepted bonus position base+n.
        tlogits_d, cache = extend(params, cache,
                                  jnp.asarray(drafts.T, jnp.int32))
        tlogits = np.asarray(tlogits_d)            # [B, n, V]

        per_acc = [0] * B
        per_extra: list = [None] * B
        for b in range(B):
            tdists = [tlast[b]] + [tlogits[b, i] for i in range(n - 1)]
            for i in range(n):
                d_i = int(drafts[i, b])
                if not temperature:
                    t_tok = int(np.argmax(tdists[i]))
                    if d_i == t_tok:
                        per_acc[b] += 1
                        continue
                    per_extra[b] = t_tok
                    break
                p = _softmax_np(tdists[i] / temperature)
                q = _softmax_np(qlogits[i, b] / temperature)
                ok, tok = _spec_accept(d_i, p, q, rng_np)
                if ok:
                    per_acc[b] += 1
                    continue
                per_extra[b] = tok
                break
        # Min-acceptance: all rows advance n_acc + 1 tokens.  A row
        # that accepted beyond n_acc takes its OWN verified draft at
        # position n_acc as the extra — values stay exactly that row's
        # target chain; only speed is lost to the slowest row.
        n_acc = min(per_acc)
        extra = [0] * B
        for b in range(B):
            if per_acc[b] > n_acc:
                extra[b] = int(drafts[n_acc, b])
            elif per_extra[b] is not None:
                extra[b] = per_extra[b]
            else:
                # Row accepted all n (== n_acc): bonus from its last
                # chunk row.
                extra[b] = _host_pick(tlogits[b, n - 1])
        accepted_total += n_acc
        for b in range(B):
            out[b].extend(int(t) for t in drafts[:n_acc, b])
        if len(out[0]) < max_new_tokens:
            for b in range(B):
                out[b].append(extra[b])
            # --- restore the invariant: feed the extra tokens -------
            # Both caches fed d_0..d_{n-1} (pos base+n).  Roll both to
            # the accepted frontier and feed `extra`; stale speculative
            # slots beyond it are masked (abs-pos reconstruction) and
            # later overwritten.
            feed = jnp.asarray(extra, jnp.int32)
            tl, cache = tstep(params, _at(cache, base + n_acc), feed)
            dl, dcache = dstep(draft_params, _at(dcache, base + n_acc),
                               feed)
            tlast, dlast = np.asarray(tl), np.asarray(dl)
            base = base + n_acc + 1
        else:
            base = base + n_acc
    toks = jnp.asarray([row[:max_new_tokens] for row in out], jnp.int32)
    stats = {"rounds": rounds,
             "accept_rate": accepted_total / max(1, proposed_total)}
    return toks, stats


def _softmax_np(x):
    e = np.exp(x - np.max(x))
    return e / e.sum()


def _spec_accept(d_tok: int, p, q, rng_np):
    """One speculative accept/resample decision (Leviathan et al.):
    accept draft token `d_tok` (drawn from q) with probability
    min(1, p[d]/q[d]); otherwise resample from norm(max(p - q, 0)).
    The emitted token is distributed EXACTLY per p — the identity the
    whole scheme rests on, property-tested in isolation
    (tests/test_decode.py::test_accept_rule_preserves_target_dist)."""
    if rng_np.uniform() < min(1.0, float(p[d_tok])
                              / max(float(q[d_tok]), 1e-20)):
        return True, int(d_tok)
    resid = np.maximum(p - q, 0.0)
    resid = resid / max(resid.sum(), 1e-20)
    return False, int(rng_np.choice(len(resid), p=resid))


# The compiled step / chunk programs CONSUME their cache argument
# (donate_argnums=(1,)): with the in-place layer walk the returned
# cache is the argument's own buffer, so a caller holds the cache once,
# must rebind the result and may never touch the argument again.  They
# stay lambdas: the benchmark's trace readers find the server's
# programs as `jit__lambda(` (PERF.md section 7 (b)).


@functools.lru_cache(maxsize=None)
def _spec_extend_fn(cfg: TransformerConfig):
    return jax.jit(lambda p, c, t: transformer_extend(p, c, t, cfg),
                   donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _spec_step_fn(cfg: TransformerConfig):
    return jax.jit(lambda p, c, t: transformer_decode_step(p, c, t, cfg),
                   donate_argnums=(1,))


def _serve_step_fn(cfg: TransformerConfig):
    """The server's step: `_spec_step_fn(cfg)`'s program with the greedy
    pick in it, `(params, cache, tokens, prev)` -> (logits [B, V] f32,
    ids [B] int32, cache), so a server step syncs on the ids and leaves
    the logits on the device.  `prev` is the `ids` this program returned
    the step before, STILL ON THE DEVICE: a row whose `tokens` entry is
    negative is fed its `prev`, so the host dispatches a step before the
    ids of the step before it have reached it (serve/server.py).  A
    caller that has synced every id leaves `prev` out.
    ONE program, and a lambda like its siblings: a pick, or a merge of
    the two feeds, dispatched beside the step would run as often as the
    step, and the trace readers take the `jit__lambda(` run most often
    for the decode step.  It is kept by the step it is built over, not by
    `cfg`: whoever clears `_spec_step_fn` to have the step traced again
    gets this one traced again too."""
    return _with_greedy_ids(_spec_step_fn(cfg))


def serve_ids_len(cfg: TransformerConfig, rows: int) -> int:
    """How many int32 `_serve_step_fn(cfg)`'s `ids` hold over `rows`
    rows: the ids, and behind them what a patterned model's expert
    layers counted (`experts.ROUTED` a sparse layer)."""
    if not cfg.patterned:
        return rows
    return rows + experts_mod.sparse_layers(cfg) * len(experts_mod.ROUTED)


@functools.lru_cache(maxsize=None)
def _with_greedy_ids(step):
    def picked(logits, cache):
        # argmax takes the first of equal maxima, as np.argmax does
        ids = jnp.argmax(logits, -1).astype(jnp.int32)
        if "routed" in cache:
            # a patterned model's expert layers' counts ride behind the
            # ids, [max_batch + sparse layers x len(experts.ROUTED)], so
            # that the step's one sync brings both
            ids = jnp.concatenate([ids, cache["routed"].reshape(-1)])
        return logits, ids, cache

    def fed(tokens, prev):
        if prev is None:
            return tokens
        return jnp.where(tokens < 0, prev[:tokens.shape[0]], tokens)

    return jax.jit(
        lambda p, c, t, prev=None: picked(*step(p, c, fed(t, prev))),
        donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _spec_draft_scan(cfg: TransformerConfig, n: int, sampled: bool):
    """One compiled program proposing n draft tokens per row: scan of
    (pick from current logits, feed, next logits).  Returns
    (drafts [n, B] int32, qlogits [n, B, V] f32, cache); consumes the
    cache argument like `_spec_step_fn`."""

    def run(params, cache, first_logits, keys, temp):
        def body(carry, key):
            cache, cur = carry                     # cur [B, V]
            if sampled:
                tok = jax.random.categorical(key, cur / temp, axis=-1)
            else:
                tok = jnp.argmax(cur, axis=-1)     # [B]
            lg, cache = transformer_decode_step(
                params, cache, tok.astype(jnp.int32), cfg)
            # qlogits only feed the sampling accept rule; the greedy
            # specialization stacks nothing.
            ys = ((tok.astype(jnp.int32), cur) if sampled
                  else tok.astype(jnp.int32))
            return (cache, lg), ys

        (cache, _), ys = lax.scan(
            body, (cache, first_logits), keys, length=n)
        if sampled:
            drafts, qlogits = ys
        else:
            drafts, qlogits = ys, None
        return ((drafts, qlogits, cache) if sampled
                else (drafts, cache))

    return jax.jit(run, donate_argnums=(1,))


#: The most rows of q and of k in one tile of the flash kernel over a
#: prompt, and the most numbers a tile of q, k or v may hold (rows x head
#: width): a grid step costs about 0.4 us whatever it holds, so a prompt
#: wants few of them (PERF.md 6, PR 44: 6144 tokens are 2304 steps a head
#: at the kernel's own 128 x 128 and 36 here), and Mosaic fits 1024 x
#: 1024 into the v5e's VMEM up to heads of 512, forward and backward
#: (the backward passes it at heads of 768, 2048 x 2048 at heads of 128).
_PROMPT_TILE = 1024
_PROMPT_TILE_NUMBERS = 1024 * 512


def prompt_tiles(T: int, d_head: int):
    """(padded length, (block_q, block_k)) of the flash kernel over a
    prompt of T tokens with heads of `d_head`: the FEWEST equal square
    tiles of at most `_PROMPT_TILE` rows that hold it, each a multiple of
    the kernel's 128.  Up to `_PROMPT_TILE` tokens that is one tile, the
    prompt padded to 128 and no further; beyond, n tiles pad by less than
    128 each, so the padding stays under 128 + T / 8 tokens (1100 tokens
    are two tiles of 640, not one of 2048 nor five of 256; 2944 = 23 x
    128 are three of 1024, padded by 128).  A window does not narrow a
    tile: under Laguna's 512 a tile of 1024 works twice the keys a row
    needs and is still the fastest (the probe: steps cost more than
    work).  Heads too wide for a tile of 1024 halve it."""
    most = _PROMPT_TILE
    while most > 128 and most * d_head > _PROMPT_TILE_NUMBERS:
        most //= 2
    n = -(-T // most)
    tile = -(-T // (128 * n)) * 128
    return n * tile, (tile, tile)


def _flash_prompt(q, k, v, window):
    """Causal attention of a whole prompt [B, T, H, D] through the flash
    kernel (ops/flash_attention.py) at the tiles `prompt_tiles` fits to
    it, the prompt padded to a whole number of them: behind a causal mask
    what is appended changes nothing before it.  No [H, T, T] scores are
    kept at any length.  Every caller's rule (a served pattern's layers,
    a latent kind's expanded form, models/pattern.py's training)."""
    from ..ops.flash_attention import flash_attention
    T = q.shape[1]
    padded, blocks = prompt_tiles(T, q.shape[-1])
    if padded != T:
        q, k, v = (jnp.pad(a, ((0, 0), (0, padded - T), (0, 0), (0, 0)))
                   for a in (q, k, v))
    return flash_attention(q, k, v, causal=True, window=window,
                           blocks=blocks)[:, :T]


def _prefill_layer(lp, ck, cv, i, x, cfg: TransformerConfig,
                   tp_axis=None):
    """Layer `i`'s attention over a whole prompt x [B, T0, D] (the
    training attention path), under `_layer_walk`'s contract: slots
    0..T0-1 of layer `i` of the stacked cache are written in place
    (`_cache_write` lays the prompt's k and v head-major, one transpose a
    layer) and nothing of the cache is read — the prompt attends to its
    own k/v, as projected."""
    dt = cfg.compute_dtype
    positions = jnp.arange(x.shape[1])
    h = _rmsnorm(lp["ln1"]["scale"], x)
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dt))
    q = _rotate(q, positions, cfg).astype(dt)
    k = _rotate(k, positions, cfg).astype(dt)

    # The prompt pass itself attends at full precision; decode
    # steps read the quantized store (documented lossy boundary).
    T0, S = x.shape[1], cache_slots(ck)
    if T0 > S:
        # A window layer's ring of a patterned model, shorter than the
        # prompt: it keeps the last S tokens, each at slot pos % S.
        ring = lambda a: jnp.roll(a[:, T0 - S:], (T0 - S) % S, axis=1)
        ck = _cache_write(ck, i, ring(k), 0)
        cv = _cache_write(cv, i, ring(v), 0)
    else:
        ck = _cache_write(ck, i, k, 0)
        cv = _cache_write(cv, i, v, 0)
    if cfg.prompt_attention == "flash" and T0 >= 128:
        with jax.named_scope("hvd.attn"):  # as training's: a trace names it
            o = _flash_prompt(q, k, v, cfg.attn_window or None)
    else:
        o = seq_mod.full_attention(q, k, v, causal=True,
                                   window=cfg.attn_window or None)
    if "w_gate" in lp:
        o = _head_gate(lp, h, o.astype(jnp.float32), cfg)
    out = jnp.einsum("bthk,hkd->btd", o.astype(dt),
                     lp["wo"].astype(dt))
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)
    return x + out.astype(x.dtype), ck, cv


def transformer_prefill(params: Dict, cache: Dict, prompt,
                        cfg: TransformerConfig, tp_axis=None,
                        chunk: int = RETENTION_CHUNK):
    """Absorb the whole prompt [B, T0] in ONE batched forward (the
    training attention path), filling ring slots 0..T0-1.  Returns
    (last-position logits [B, V], cache).  Requires a fresh cache
    (pos == 0) and T0 <= max_len.  `chunk` is a retention model's
    chunk length; the result does not depend on it, and only the tests
    of that pass another."""
    dt = cfg.compute_dtype
    B, T0 = prompt.shape
    if B < 1 or T0 < 1:
        raise InvalidRequestError(
            f"prompt must be non-empty, got shape {(B, T0)} (an empty "
            "prefill would silently leave the cache desynced)")
    kind = _kind(cfg)
    ka, kb = kind.leaves
    if kind.slots and T0 > (S := cache_slots(cache[ka])):
        # (a patterned model's longest ring; its window layers' rings
        # keep a prompt's last `window` tokens)
        raise InvalidRequestError(
            f"prompt length {T0} > cache max_len {S}")
    # Prefill writes the prompt at slot 0; a warm cache (pos != 0)
    # would silently desync slot <-> absolute-position bookkeeping.
    # Enforce eagerly whenever pos is concrete (inside jit pos is a
    # tracer and the contract is on the caller).
    if not isinstance(cache["pos"], jax.core.Tracer):
        if int(cache["pos"]) != 0:
            raise ValueError(
                f"transformer_prefill requires a fresh cache "
                f"(pos == 0), got pos = {int(cache['pos'])}")
    x = params["embed"][prompt].astype(dt)                # [B,T0,D]
    routed = []
    x, ck, cv = _layer_walk(
        params, cache[ka], cache[kb], x,
        functools.partial(kind.prefill, cfg=cfg, tp_axis=tp_axis,
                          chunk=chunk),
        cfg, tp_axis, None, routed)
    x = _rmsnorm(params["final_norm"]["scale"], x[:, -1:])
    logits = jnp.einsum("bod,vd->bov", x.astype(dt),
                        params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    return logits[:, 0], {ka: ck, kb: cv,
                          "pos": cache["pos"] + T0,
                          **_routed(cfg, routed)}


def _resolve_max_len(cfg, T0, max_new_tokens, max_len):
    """Shared generate/beam cache-capacity rule: default to the full
    sequence; allow a smaller rolling ring only for windowed configs."""
    max_len = max_len or (T0 + max_new_tokens)
    if T0 + max_new_tokens > max_len:
        if not cfg.attn_window:
            raise InvalidRequestError(
                f"max_len {max_len} < prompt {T0} + new "
                f"{max_new_tokens} (only windowed configs may roll "
                f"the cache)")
        if max_len < cfg.attn_window:
            raise InvalidRequestError(
                f"max_len {max_len} < attn_window {cfg.attn_window} "
                f"and the sequence ({T0} + {max_new_tokens} tokens) "
                f"wraps the ring: positions still inside the band "
                f"would be evicted — size max_len >= "
                f"max(attn_window, prompt length) = "
                f"{max(cfg.attn_window, T0)}")
    return max_len


def transformer_generate(params: Dict, cfg: TransformerConfig, prompt,
                         max_new_tokens: int,
                         temperature: float = 0.0,
                         top_p: float = 1.0,
                         top_k: int = 0,
                         eos_id: Optional[int] = None,
                         rng: Optional[jax.Array] = None,
                         max_len: Optional[int] = None,
                         quantize=None) -> Tuple[jax.Array, Dict]:
    """Generate `max_new_tokens` continuations of `prompt` [B, T0].

    Greedy when temperature == 0 (default), else softmax sampling at
    the given temperature (requires `rng`); `top_p < 1` restricts
    sampling to the smallest set of tokens whose cumulative probability
    reaches top_p (nucleus sampling); `top_k > 0` restricts it to the k
    highest-probability tokens.  Both may be combined (top-k cut first,
    then the nucleus within it — the usual composition).  Returns
    (tokens [B, max_new_tokens], final cache).  Prefill is one batched
    forward; generation is one `lax.scan` — two compiled programs
    total.

    `eos_id`: rows that emit this token stop — every position strictly
    after a row's first eos is reported as `eos_id` (padding).  The
    scan still runs max_new_tokens steps (static shapes; the tail
    compute is discarded, not skipped — XLA has no data-dependent
    early exit).

    `max_len` defaults to T0 + max_new_tokens; with `cfg.attn_window`
    it may be as small as max(window, T0) — the ring rolls."""
    B, T0 = prompt.shape
    if B < 1 or T0 < 1:
        raise InvalidRequestError(
            f"prompt must be non-empty, got shape {(B, T0)}")
    if max_new_tokens < 1:
        raise InvalidRequestError(
            f"max_new_tokens must be >= 1, got {max_new_tokens} (a "
            "zero-length scan would silently return an empty batch)")
    if not _kind(cfg).slots:
        max_len = 1                  # a state a row: no ring to size
    else:
        max_len = _resolve_max_len(cfg, T0, max_new_tokens, max_len)
        if max_len < T0:
            raise InvalidRequestError(
                f"max_len {max_len} < prompt length {T0}: the prefill "
                "would overrun the ring before the first generated token")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0 or top_k > cfg.vocab_size:
        raise ValueError(
            f"top_k must be in [0, vocab_size], got {top_k}")
    if (top_p < 1.0 or top_k) and not temperature:
        raise ValueError(
            "top_p < 1 / top_k > 0 need temperature > 0 (greedy "
            "decoding ignores them)")
    if eos_id is not None and not 0 <= int(eos_id) < cfg.vocab_size:
        raise ValueError(
            f"eos_id {eos_id} outside vocab [0, {cfg.vocab_size})")
    cache = init_decode_cache(cfg, B, max_len, quantize=quantize)
    last_logits, cache = transformer_prefill(params, cache, prompt, cfg)

    def pick(logits, key):
        if not temperature:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_p < 1.0 or top_k:
            # Truncated sampling IN SORTED SPACE (mask the tail ranks,
            # draw a rank, map back through sort_idx) — same
            # distribution as masking in vocab order, without paying a
            # per-token O(B*V) scatter inside the generation scan.
            sort_idx = jnp.argsort(-logits, axis=-1)
            sorted_logits = jnp.take_along_axis(logits, sort_idx, -1)
            if top_k:
                # Top-k cut FIRST; the nucleus then applies to the
                # RENORMALIZED top-k distribution (softmax over the
                # surviving ranks) — the HF warper-chain composition
                # the docstring promises.
                sorted_logits = jnp.where(
                    jnp.arange(logits.shape[-1]) < top_k,
                    sorted_logits, -jnp.inf)
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep ranks where the cumulative mass BEFORE them < top_p
            # (rank 0 always kept — no all-masked row exists; ranks cut
            # by top-k carry -inf logits and stay cut regardless)
            keep_sorted = (cum - probs) < top_p
            masked = jnp.where(keep_sorted, sorted_logits, -jnp.inf)
            rank = jax.random.categorical(key, masked)
            return jnp.take_along_axis(
                sort_idx, rank[:, None], -1)[:, 0]
        return jax.random.categorical(key, logits)

    keys = (jax.random.split(rng, max_new_tokens) if rng is not None
            else jnp.zeros((max_new_tokens, 2), jnp.uint32))

    def gen_step(carry, key):
        cache, logits = carry
        tok = pick(logits, key)
        logits, cache = transformer_decode_step(params, cache, tok, cfg)
        return (cache, logits), tok

    (cache, _), toks = lax.scan(gen_step, (cache, last_logits), keys)
    toks = toks.T                                         # [B, max_new]
    if eos_id is not None:
        hit = toks == eos_id
        # Strictly after each row's FIRST eos: the cumulative count
        # BEFORE the position is already positive.
        after = (jnp.cumsum(hit, axis=1) - hit.astype(jnp.int32)) > 0
        toks = jnp.where(after, jnp.asarray(eos_id, toks.dtype), toks)
    return toks, cache


class ShardedDecode(NamedTuple):
    """Sharded inference bundle from `make_decode_step`.  Unpacks as
    (step, prefill, shard_params, shard_cache, shard_tokens, extend);
    `extend` is the chunked multi-token forward (the speculative verify
    pass), sharded identically to `step`."""

    step: Any
    prefill: Any
    shard_params: Any
    shard_cache: Any
    shard_tokens: Any
    extend: Any


def make_decode_step(mesh, cfg: TransformerConfig, quantize=None):
    """Sharded inference: build a `ShardedDecode` bundle (decode step,
    prefill, chunked extend, sharding helpers) over a dp x tp mesh.

    - batch shards over `dp`; attention heads and the KV cache's head
      axis shard over `tp` (n_heads % tp == 0 and kv_heads % tp == 0 —
      the GQA+TP constraint from transformer_pspecs);
    - wo/wd are row-parallel (one psum per layer, the decode analog of
      the training block's tensor parallelism);
    - `ep` is not supported at decode (MoE weights stay replicated and
      route with the no-capacity inference semantics);
    - `step`, `prefill` and `extend` CONSUME their cache argument
      (donated: the in-place layer walk returns it in the same
      buffers), so rebind it — `logits, sc = step(sp, sc, tokens)` —
      and never read the argument again.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .transformer import transformer_pspecs

    _needs_slots(cfg, "make_decode_step (dp/tp sharding of the "
                      "state)")
    _needs_uniform(cfg, "make_decode_step (dp/tp sharding)")
    axes = {a: mesh.shape.get(a, 1) > 1 for a in mesh.axis_names}
    if axes.get("ep") and cfg.moe_every:
        raise NotImplementedError(
            "expert-parallel decode is not supported; decode MoE runs "
            "replicated (drop ep from the mesh)")
    if axes.get("pp") or axes.get("sp"):
        raise NotImplementedError(
            "decode shards over dp/tp only (no pp/sp schedule at "
            "one-token granularity)")
    tp_axis = "tp" if axes.get("tp") else None
    dp = "dp" if axes.get("dp") else None

    def _clean(spec):
        # transformer_pspecs names tp/ep unconditionally; drop axes the
        # inference mesh doesn't carry.
        def keep(e):
            if isinstance(e, tuple):
                kept = tuple(a for a in e if a in mesh.axis_names)
                return kept or None
            return e if (e is None or e in mesh.axis_names) else None
        return P(*[keep(e) for e in spec])

    pspecs = jax.tree_util.tree_map(
        _clean, transformer_pspecs(cfg, 1),
        is_leaf=lambda x: isinstance(x, P))
    tok_spec = P(dp)
    logits_spec = P(dp, None)
    kv_spec = P(None, dp, tp_axis, None, None)
    if quantize is not None:    # int8 and fp8_e4m3 share the layout
        kv_spec = {"q": kv_spec, "scale": P(None, dp, tp_axis, None)}
    cache_spec = {"k": kv_spec, "v": kv_spec, "pos": P()}

    step = jax.jit(shard_map(
        lambda p, c, t: transformer_decode_step(p, c, t, cfg, tp_axis),
        mesh=mesh, in_specs=(pspecs, cache_spec, tok_spec),
        out_specs=(logits_spec, cache_spec), check_vma=False),
        donate_argnums=(1,))
    prefill = jax.jit(shard_map(
        lambda p, c, t: transformer_prefill(p, c, t, cfg, tp_axis),
        mesh=mesh,
        in_specs=(pspecs, cache_spec, P(dp, None)),
        out_specs=(logits_spec, cache_spec), check_vma=False),
        donate_argnums=(1,))
    extend = jax.jit(shard_map(
        lambda p, c, t: transformer_extend(p, c, t, cfg, tp_axis),
        mesh=mesh,
        in_specs=(pspecs, cache_spec, P(dp, None)),
        out_specs=(P(dp, None, None), cache_spec), check_vma=False),
        donate_argnums=(1,))

    def shard_params(params):
        return jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, pspecs)

    def shard_cache(cache):
        return jax.tree_util.tree_map(
            lambda v, sp: jax.device_put(v, NamedSharding(mesh, sp)),
            cache, cache_spec)

    def shard_tokens(tokens):
        return jax.device_put(tokens, NamedSharding(mesh, tok_spec))

    return ShardedDecode(step, prefill, shard_params, shard_cache,
                         shard_tokens, extend)


def transformer_beam_search(params: Dict, cfg: TransformerConfig,
                            prompt, max_new_tokens: int,
                            beam_width: int = 4,
                            length_penalty: float = 0.0,
                            eos_id: Optional[int] = None,
                            max_len: Optional[int] = None,
                            quantize=None):
    """Beam search over the KV-cache decode path.

    prompt [B, T0] -> (tokens [B, W, max_new], scores [B, W]) sorted
    best-first; scores are sums of chosen-token logprobs.

    Without `eos_id`, all beams decode the full max_new_tokens and
    `length_penalty` only NORMALIZES the reported scores
    (score / max_new**penalty, the GNMT formula).  With `eos_id`, a
    beam that emits it is FINISHED: it keeps its score (subsequent
    forced-eos continuations add logprob 0) and its reported tail reads
    eos_id; `length_penalty` then normalizes the W SURVIVORS by their
    ACTUAL lengths (first-eos position + 1) and re-sorts.  Caveat:
    during the search itself beams compete on RAW scores — a short
    finished hypothesis whose raw sum falls below W live continuations
    is evicted before the final re-rank (no separate finished pool, the
    in-scan tradeoff; HF-style finished-pool semantics would need
    2W-candidate bookkeeping).

    The cache carries B*W rows (beam-major within batch); each step
    selects the top-W of the W*V continuations per batch and GATHERS
    the parent beams' cache rows, the standard reorder.  One lax.scan.
    """
    _needs_slots(cfg, "beam search (reordering beams copies "
                      "their states)")
    _needs_uniform(cfg, "beam search")
    B, T0 = prompt.shape
    W = int(beam_width)
    if W < 1:
        raise ValueError(f"beam_width must be >= 1, got {W}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    V = cfg.vocab_size
    if eos_id is not None and not 0 <= int(eos_id) < V:
        raise ValueError(f"eos_id {eos_id} outside vocab [0, {V})")
    max_len = _resolve_max_len(cfg, T0, max_new_tokens, max_len)

    # Prefill ONCE per sequence, then tile each cache row W times
    # (beam-major: row b*W + w is beam w of sequence b).
    cache = init_decode_cache(cfg, B, max_len, quantize=quantize)
    logits, cache = transformer_prefill(params, cache, prompt, cfg)

    def tile(t):
        return jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, W, axis=1), t)

    cache = {"k": tile(cache["k"]), "v": tile(cache["v"]),
             "pos": cache["pos"]}
    logp = jax.nn.log_softmax(logits, axis=-1)              # [B, V]
    # First step: top-W distinct tokens seed the beams.
    seed_lp, seed_tok = jax.lax.top_k(logp, W)              # [B, W]
    scores = seed_lp.reshape(B * W)
    tok = seed_tok.reshape(B * W)
    done = (tok == eos_id) if eos_id is not None else \
        jnp.zeros((B * W,), bool)
    if eos_id is not None:
        # A finished beam's only continuation is eos at logprob 0: its
        # score freezes and the tail reads eos.
        frozen_lp = jnp.full((V,), -1e30).at[int(eos_id)].set(0.0)

    def gen_step(carry, _):
        cache, scores, tok, done = carry
        logits, cache = transformer_decode_step(params, cache, tok, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)          # [B*W, V]
        if eos_id is not None:
            logp = jnp.where(done[:, None], frozen_lp[None, :], logp)
        cand = scores[:, None] + logp                       # [B*W, V]
        cand = cand.reshape(B, W * V)
        new_scores, flat_idx = jax.lax.top_k(cand, W)       # [B, W]
        parent = flat_idx // V                              # beam index
        new_tok = flat_idx % V
        # Gather parent beams' cache rows (batch-major offsets).
        rows = (jnp.arange(B)[:, None] * W + parent).reshape(B * W)
        gather = lambda t: jax.tree_util.tree_map(
            lambda a: a[:, rows], t)
        cache = {"k": gather(cache["k"]), "v": gather(cache["v"]),
                 "pos": cache["pos"]}
        new_tok_flat = new_tok.reshape(B * W)
        new_done = done[rows]
        if eos_id is not None:
            new_done = new_done | (new_tok_flat == eos_id)
        return ((cache, new_scores.reshape(B * W),
                 new_tok_flat, new_done),
                (new_tok_flat, rows))

    (cache, scores, tok, done), (toks, parents) = lax.scan(
        gen_step, (cache, scores, tok, done), None,
        length=max_new_tokens - 1)

    # Reconstruct each surviving beam's token path by walking the
    # parent pointers backward (host-side numpy — the scan above is the
    # compiled part; this makes transformer_beam_search eager-only).
    toks = jnp.concatenate([seed_tok.reshape(1, B * W), toks], axis=0)
    paths = np.zeros((max_new_tokens, B * W), np.int64)
    live = np.arange(B * W)
    toks_np = np.asarray(toks)
    parents_np = np.asarray(parents)
    for t in range(max_new_tokens - 1, 0, -1):
        paths[t] = toks_np[t, live]
        live = parents_np[t - 1, live]
    paths[0] = toks_np[0, live]
    out = jnp.asarray(paths.T).reshape(B, W, max_new_tokens)
    scores = scores.reshape(B, W)
    if length_penalty:
        if eos_id is not None:
            # Actual lengths: first eos + 1 (max_new when no eos) —
            # the penalty genuinely re-ranks unequal-length beams.
            out_np = np.asarray(out)
            hit = out_np == int(eos_id)
            lengths = np.where(hit.any(axis=-1),
                               hit.argmax(axis=-1) + 1,
                               max_new_tokens).astype(np.float64)
            scores = scores / jnp.asarray(lengths ** length_penalty,
                                          scores.dtype)
            order = jnp.argsort(-scores, axis=-1)
            scores = jnp.take_along_axis(scores, order, -1)
            out = jnp.take_along_axis(out, order[..., None], 1)
        else:
            # Equal-length beams: a pure normalization of the reported
            # scores (see docstring) — ranking is unchanged.
            scores = scores / (float(max_new_tokens) ** length_penalty)
    # Sorted best-first: lax.top_k emits descending scores; the
    # equal-length normalization is order-preserving, and the
    # eos-length path re-sorts explicitly above.
    return out, scores


__all__ = ["init_decode_cache", "cache_leaves", "cache_slots",
           "retention_features",
           "transformer_decode_step",
           "transformer_prefill", "transformer_extend",
           "transformer_generate", "transformer_speculative_generate",
           "transformer_beam_search", "make_decode_step",
           "ShardedDecode"]
