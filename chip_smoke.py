"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # from the repo root, on a machine with a TPU

One process drives the main paths once through the entry points a user
calls, at the full width of the models the repo supports, and checks
each result by the repo's own means:

  resnet       ResNet-50 (1000 classes, 224 px, bf16, 256 images a chip)
               driven as examples/synthetic_benchmark.py drives it:
               hvd.init -> DistributedOptimizer -> broadcast_parameters
               -> data_parallel -> shard_batch; loss finite and falling.
  transformer  make_train_step + AdamW over create_hybrid_mesh at d_model
               2048, 16 heads x 128, d_ff 8192, vocab 50,304, T 2048,
               bf16.  Depth and batch are CUT to what 16 GB holds with no
               recomputation; no width is.  It is not a named model.
  server       InferenceServer at the same widths: a dozen requests of
               mixed prompt length, pool drained, every emitted token
               within a logit tolerance of transformer_generate's path;
               then the decode step compiled at a stacked view, its
               temporaries under one layer's K slice (no layer's K or V
               is copied out of the view before its contraction).
  kernels      every pl.pallas_call site compiled by Mosaic and compared
               with its XLA oracle, including the automatic flash route
               at T = 16384.

With several chips it also checks that the work is really spread over
them (sizes come from the device count found, so one chip and four run
the same file).  There is no CPU mode: on any platform but a TPU the
script exits non-zero before the first phase.  Phases are plain
functions of their sizes so tests/test_chip_smoke.py can call them tiny
on the CPU mesh.  The last line of stdout is one JSON object.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.common.util import configure_compile_cache
from horovod_tpu.models import (
    TransformerConfig,
    init_decode_cache,
    make_train_step,
    resnet_apply,
    resnet_init,
    transformer_decode_step,
    transformer_generate,
    transformer_init,
    transformer_prefill,
)
from horovod_tpu.models.decode import _spec_step_fn
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops import decode_attention, retention_step
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.fused_collectives import pallas_matmul
from horovod_tpu.parallel import (
    create_hybrid_mesh,
    dense_attention_oracle,
    full_attention,
)
from horovod_tpu.serve import InferenceServer

# --- sizes -----------------------------------------------------------------

RESNET = dict(depth=50, classes=1000, image=224, per_chip=256, steps=6)

# Attention and embedding widths of ROADMAP R1's target.  The cut: 4
# layers and a global batch of 2 sequences.  f32 params + grads + Adam
# state are 16 B/param (1.65 GB for the embedding, 1.07 GB a layer) and,
# with no recomputation, every layer keeps f32 [B, 16, 2048, 2048]
# scores for the backward pass.  XLA's memory analysis of this step
# compiled for a v5e gives 8.9 GiB at 4 layers and 12.1 GiB at 6, which
# leaves too little of the chip's 15.75 GiB for what else is resident.
LM = dict(vocab_size=50304, d_model=2048, n_heads=16, d_head=128,
          d_ff=8192, n_layers=4)
LM_BATCH, LM_SEQ, LM_STEPS = 2, 2048, 4

SERVE = dict(prompt_lens=(24, 96, 160, 256), n_requests=12,
             max_new=(6, 10), max_batch=4, page_tokens=16)
# The decode view the step is compiled at for `phase_decode_layout`, and
# the kv heads it groups the 16 query heads under.  No smaller: compiled
# for a v5e, the slot-major cache of PR 27 copied a layer's K and V out
# only where a slice held 128 MB or more and the heads were grouped
# (32 x 4096 here: 134 MB copied, 32 x 3584: none; PERF.md, PR 29), so a
# smaller view would pass whatever the layout.  Nothing is allocated.
DECODE_VIEW = dict(rows=32, slots=4096, kv_heads=4)

# Flash attention forward and backward, bf16, B 1, at KERNEL_SEQ: heads,
# d_head and what else `_attention_case` takes.
FLASH_CASES = {
    "flash d128": dict(H=4, D=128),
    "flash d64": dict(H=4, D=64),
    "flash blocks 256": dict(H=4, D=128, block=256),
    "flash window 512": dict(H=4, D=128, window=512),
    "flash 3 segments": dict(H=4, D=128, segments=3),
    "flash gqa4": dict(H=8, D=128, kv_heads=2),
}
KERNEL_SEQ, KERNEL_LONG_SEQ = 2048, 16384

# The decode step's read of the view (ops/decode_attention.py), bf16, 8 kv
# heads of 128 in a stack of 2 layers: rows at ragged depths with idle
# ones between, a view that is no multiple of the block, a wrapped ring.
DECODE_CASES = {
    "decode attention g4": dict(slots=3584, group=4, window=4096,
                                pos=[0, 1000, 0, 3583, 17, 0, 2047, 512]),
    "decode attention g6 3840": dict(slots=3840, group=6, window=0,
                                     pos=[3839, 0, 3600, 1, 0, 700]),
    "decode attention wrapped": dict(slots=1024, group=4, window=1000,
                                     pos=[5000, 1024, 0, 1023]),
}

# A retention layer's pass over the state (ops/retention_step.py), float32,
# heads of 128 (a state of 8320 x 128 a kv head) at layer 1 of a stack of
# 2: idle rows between the live ones and at both ends.
STATE_CASES = {
    "retention step g5": dict(live=[0, 1, 1, 0, 1, 0], kv_heads=8, group=5),
}

# Server vs transformer_generate, in logit units (logits here are O(1):
# unit-RMS activations against a 1/sqrt(d) embedding).  The server
# decodes max_batch rows over a paged view, the reference one row over a
# contiguous cache; XLA tiles the two differently and bf16 operands keep
# 8 mantissa bits (2^-8 = 4e-3 relative per rounding), so the same logit
# differs by O(1e-2) and the arg-max may flip between near-ties.  A
# token is right when the reference scores it within this of its best.
SERVE_LOGIT_TOL = 0.1

# Kernel vs f32 oracle, as max|a - b| / max|b|.  Everything that goes
# through the MXU is rounded to bf16 on the way in, 2^-8 = 4e-3 each:
# flash attention's p and ds before their matmuls and its output once
# more, chained and accumulated in another order; pallas_matmul's f32
# operands too, because Mosaic's dot runs at the MXU's default precision.
MXU_KERNEL_TOL = 2e-2
# The Adasum kernels are f32 on the vector unit; what differs is the
# order of a 300k-element sum.  The retention step's read-out is a
# float32 product at "highest", its update f32 on the vector unit.
F32_KERNEL_TOL = 1e-4

# Sharded vs one-chip loss for the same seed and global batch: the same
# bf16 math with the reductions split over chips.
SHARDED_LOSS_RTOL = 2e-2


class SmokeFailure(Exception):
    def __init__(self, phase: str, msg: str):
        super().__init__(f"phase {phase}: {msg}")


def _check(cond, phase: str, msg: str) -> None:
    if not cond:
        raise SmokeFailure(phase, msg)


class CompileMeter:
    """Seconds spent in, and persistent-cache hits of, XLA compilation
    since the last `take()` — set-up time, reported beside each phase."""

    def __init__(self):
        self.secs, self.requests, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.secs, 2), "programs": self.requests,
               "cache_hits": self.hits}
        self.secs, self.requests, self.hits = 0.0, 0, 0
        return out


def _falling(losses, phase: str) -> None:
    _check(all(np.isfinite(losses)), phase, f"loss not finite: {losses}")
    _check(losses[-1] < losses[0], phase, f"loss did not fall: {losses}")


def _timed_steps(step, carry, batch, n: int):
    """n calls of step(*carry, batch) -> (*carry, loss); returns the
    carry, the losses, the first call's seconds (compilation included)
    and the median of the rest."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        *carry, loss = step(*carry, batch)
        losses.append(float(jax.block_until_ready(loss)))
        secs.append(time.perf_counter() - t0)
    return carry, losses, secs[0], float(np.median(secs[1:]))


# --- trainer ---------------------------------------------------------------

def phase_collectives() -> dict:
    """Eager allreduce over every rank: the average of 0..n-1."""
    n = hvd.size()
    out = hvd.allreduce(hvd.PerRank(
        [np.full((3,), r, np.float32) for r in range(n)]))
    got = np.asarray(out)
    want = (n - 1) / 2
    _check(np.allclose(got, want), "collectives",
           f"allreduce of ranks 0..{n - 1} gave {got}, want {want}")
    return {"size": n, "allreduce": float(got.ravel()[0])}


def phase_resnet(depth: int, classes: int, image: int, per_chip: int,
                 steps: int) -> dict:
    n = hvd.size()
    v = resnet_init(jax.random.PRNGKey(0), depth, num_classes=classes)
    cfg = v["config"]
    state = {"params": v["params"], "batch_stats": v["batch_stats"]}
    opt = hvd.DistributedOptimizer(optax.sgd(0.01 * n, momentum=0.9))
    opt_state = opt.init(state["params"])
    state["params"] = hvd.broadcast_parameters(state["params"], root_rank=0)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(per_chip * n, image, image, 3)
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, classes, size=per_chip * n))

    @hvd.data_parallel
    def step(state, opt_state, batch):
        xb, yb = batch

        def loss_fn(p):
            logits, ns = resnet_apply(
                {"params": p, "batch_stats": state["batch_stats"],
                 "config": cfg},
                xb, train=True, compute_dtype=jnp.bfloat16,
                axis_name=hvd.GLOBAL_AXIS)
            onehot = jax.nn.one_hot(yb, classes)
            loss = -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
            return loss, ns

        (loss, ns), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        updates, opt_state2 = opt.update(grads, opt_state, state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "batch_stats": ns}, opt_state2, loss

    batch = hvd.shard_batch((x, y))
    (state, opt_state), losses, first_s, step_s = _timed_steps(
        step, (state, opt_state), batch, steps)
    _falling(losses, "resnet")

    # The work is spread: the batch's shards and the updated parameters
    # sit on n distinct devices, each device holds memory, and the
    # replicated parameters are bitwise equal everywhere.
    leaves = jax.tree_util.tree_leaves(state["params"])
    batch_devs = {s.device for s in batch[0].addressable_shards}
    param_devs = {s.device for s in leaves[0].addressable_shards}
    _check(len(batch_devs) == n and len(param_devs) == n, "resnet",
           f"batch on {len(batch_devs)} and params on {len(param_devs)} "
           f"devices, want {n}")
    mem = [d.memory_stats() for d in hvd.global_devices()]
    if jax.default_backend() == "tpu":
        _check(all(m["bytes_in_use"] > 0 for m in mem), "resnet",
               f"a device reports no memory in use: {mem}")
    for leaf in leaves:
        first, *rest = (np.asarray(s.data) for s in leaf.addressable_shards)
        _check(all(first.tobytes() == r.tobytes() for r in rest), "resnet",
               "replicated parameters differ between devices")
    return {"losses": [round(v, 4) for v in losses],
            "global_batch": per_chip * n,
            "first_call_s": round(first_s, 2), "step_s": round(step_s, 4)}


# --- transformer step ------------------------------------------------------

def phase_transformer(cfg: TransformerConfig, batch: int, seq: int,
                      steps: int, devices, **mesh_axes) -> dict:
    """`steps` AdamW steps of make_train_step on a resident batch over a
    hybrid mesh of `devices` (mesh_axes: dp/tp/sp/... degrees)."""
    mesh = create_hybrid_mesh(devices=devices, **mesh_axes)
    opt = optax.adamw(3e-4)
    step, shard_state, shard_batch = make_train_step(mesh, cfg, opt)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    params, opt_state = shard_state(params, opt.init(params))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    lm_batch = shard_batch((tokens[:, :-1], tokens[:, 1:]))
    _, losses, first_s, step_s = _timed_steps(
        step, (params, opt_state), lm_batch, steps)
    tag = "transformer " + "x".join(f"{a}{d}" for a, d in mesh_axes.items())
    _falling(losses, tag)
    return {"mesh": dict(mesh_axes), "losses": [round(v, 4) for v in losses],
            "first_call_s": round(first_s, 2), "step_s": round(step_s, 4)}


def phase_transformer_sharded(cfg, batch, seq, steps, one_chip: dict) -> list:
    """The same step on dp x tp and on dp x sp (ring attention) over all
    the chips; each loss within SHARDED_LOSS_RTOL of the one-chip run."""
    devices = jax.devices()
    out = []
    for axes in (dict(dp=len(devices) // 2, tp=2),
                 dict(dp=len(devices) // 2, sp=2)):
        rec = phase_transformer(cfg, batch, seq, steps, devices, **axes)
        _check(np.allclose(rec["losses"], one_chip["losses"],
                           rtol=SHARDED_LOSS_RTOL), "transformer",
               f"{axes} losses {rec['losses']} vs one chip "
               f"{one_chip['losses']} (rtol {SHARDED_LOSS_RTOL})")
        out.append(rec)
    return out


# --- server ----------------------------------------------------------------

def phase_server(cfg: TransformerConfig, prompt_lens, n_requests: int,
                 max_new, max_batch: int, page_tokens: int,
                 tol: float) -> dict:
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    max_seq = max(prompt_lens) + max(max_new)
    srv = InferenceServer(params, cfg, max_seq_tokens=max_seq,
                          max_batch=max_batch, page_tokens=page_tokens)
    rng = np.random.RandomState(1)
    prompts = {}
    for i in range(n_requests):
        prompt = rng.randint(0, cfg.vocab_size,
                             size=prompt_lens[i % len(prompt_lens)])
        prompts[srv.submit(prompt, max_new[i % len(max_new)])] = prompt
    t0 = time.perf_counter()
    done = {s.req.req_id: s.generated for s in srv.run()}
    run_s = time.perf_counter() - t0
    _check(sorted(done) == sorted(prompts), "server",
           f"answered {sorted(done)} of {sorted(prompts)}")
    _check(srv.pool.pages_free() == srv.pool.total_pages, "server",
           f"pool not drained: {srv.pool.pages_free()} of "
           f"{srv.pool.total_pages} pages free")

    # The reference is transformer_generate: its tokens for the agreement
    # count (greedy decoding is prefix-consistent, so the longest budget
    # serves every request), and its own path — prefill, then one
    # decode_step a token — walked along the server's tokens for how far
    # below its best token the reference scores each emitted one.
    generate = jax.jit(lambda p, t: transformer_generate(
        p, cfg, t, max(max_new))[0])
    prefill = jax.jit(lambda p, c, t: transformer_prefill(p, c, t, cfg))
    decode = jax.jit(lambda p, c, t: transformer_decode_step(p, c, t, cfg))
    worst, equal, total = 0.0, 0, 0
    for rid, prompt in prompts.items():
        tokens = done[rid]
        _check(len(tokens) == max_new[rid % len(max_new)], "server",
               f"request {rid} got {len(tokens)} tokens")
        ref = np.asarray(generate(params, jnp.asarray(prompt[None])))[0]
        equal += int(np.sum(ref[:len(tokens)] == tokens))
        total += len(tokens)
        logits, cache = prefill(params, init_decode_cache(cfg, 1, max_seq),
                                jnp.asarray(prompt[None]))
        for tok in tokens:
            row = np.asarray(logits[0])
            worst = max(worst, float(row.max() - row[tok]))
            logits, cache = decode(params, cache,
                                   jnp.asarray([tok], jnp.int32))
    _check(worst <= tol, "server",
           f"an emitted token scores {worst:.4f} below the reference's "
           f"best (tolerance {tol})")
    return {"requests": n_requests, "tokens": srv.tokens_out,
            "device_steps": srv.device_steps,
            "worst_logit_margin": round(worst, 5), "logit_tol": tol,
            "tokens_equal_to_generate": f"{equal}/{total}",
            "run_s": round(run_s, 2)}


def phase_decode_layout(cfg: TransformerConfig, rows: int, slots: int,
                        kv_heads: int) -> dict:
    """The server's decode step (vector `pos`) compiled for this backend
    at a view of `rows` x `slots` under `kv_heads` kv heads, nothing run.  The cache is held
    head-major so that the step's contractions read a layer's slice of
    the view where it lies (models/decode.py); if the compiler ever
    copies the slice out again, the program's temporaries hold at least
    one of it.  On a TPU that fails the phase; the CPU's compiler fuses
    otherwise and is only reported."""
    cfg = dataclasses.replace(cfg, n_kv_heads=kv_heads)
    # weights in the compute dtype, as a server holds them: f32 ones
    # would be cast inside the program, into temporaries of their own
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.compute_dtype),
        transformer_init(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, rows, slots))
    cache["pos"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    compiled = _spec_step_fn(cfg).lower(
        params, cache, jax.ShapeDtypeStruct((rows,), jnp.int32)).compile()
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
    k = cache["k"]
    k_slice = k.size // k.shape[0] * k.dtype.itemsize
    if jax.default_backend() == "tpu":
        _check(temp < k_slice, "decode layout",
               f"the step's temporaries, {temp} B, hold a layer's K slice "
               f"({k_slice} B): a copy precedes the contraction")
    return {"view": list(k.shape), "temp_bytes": temp,
            "layer_k_slice_bytes": k_slice}


# --- kernels ---------------------------------------------------------------

def _rel_err(got, want) -> float:
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@contextlib.contextmanager
def _flash_blocks(block):
    """The library's own block-size knobs, set for one case."""
    names = ("HOROVOD_FLASH_BLOCK_Q", "HOROVOD_FLASH_BLOCK_K")
    saved = {n: os.environ.get(n) for n in names}
    if block is not None:
        os.environ.update({n: str(block) for n in names})
    try:
        yield
    finally:
        for n, v in saved.items():
            if v is None:
                os.environ.pop(n, None)
            else:
                os.environ[n] = v


def _attention_case(attn, B, T, H, D, kv_heads=None, window=None,
                    segments=0, block=None) -> float:
    """Forward and all three gradients of `attn` against the dense oracle
    at full matmul precision; returns the worst relative error."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, T, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, T, kv_heads or H, D), jnp.bfloat16)
            for key in keys[1:])
    kw = dict(causal=True, window=window)
    if segments:
        kw["segment_ids"] = jnp.broadcast_to(
            jnp.arange(T) * segments // T, (B, T))

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v, **kw)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out, *grads)

    with _flash_blocks(block):
        got = run(attn)
    with jax.default_matmul_precision("highest"):
        want = run(dense_attention_oracle)
    return max(_rel_err(g, w) for g, w in zip(got, want))


def _decode_case(slots, group, window, pos, kv_heads=8, d_head=128,
                 block=decode_attention.BLOCK):
    """`decode_attention` at layer 1 of a stack of 2 against the plain
    softmax over every slot, masked on the slot's absolute position."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B = len(pos)
    q = jax.random.normal(ks[0], (B, kv_heads, group, d_head), jnp.bfloat16)
    ck, cv = (jax.random.normal(k, (2, B, kv_heads, slots, d_head),
                                jnp.bfloat16) for k in ks[1:])
    pos = jnp.asarray(pos, jnp.int32)
    got = jax.jit(lambda *a: decode_attention.decode_attention(
        *a, window=window, block=block))(q, ck, cv, 1, pos)
    held = pos[:, None] - (pos[:, None] - jnp.arange(slots)[None]) % slots
    valid = held >= 0
    if window:
        valid &= pos[:, None] - held < window
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                       ck[1].astype(jnp.float32)) / d_head ** 0.5
        p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -1e30), -1)
        want = jnp.einsum("bhgk,bhkd->bhgd", p, cv[1].astype(jnp.float32))
    return _rel_err(got, want)


def _state_case(live, kv_heads, group, d_head=128):
    """`retention_step` at layer 1 of a stack of 2 against the pass
    written plainly (read-out of the state as it was, then decay and
    phi(k) v^T), the live rows' four results and the idle rows' state."""
    feats = (d_head // 2 + 1) * d_head
    B, lv = len(live), np.asarray(live, bool)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    fq = jax.random.normal(ks[0], (B, kv_heads, group, feats), jnp.float32)
    fk = jax.random.normal(ks[1], (B, kv_heads, feats), jnp.float32)
    v = jax.random.normal(ks[2], (B, kv_heads, d_head), jnp.float32)
    decay = jax.random.uniform(ks[3], (B, kv_heads), jnp.float32)
    cs = jax.random.normal(ks[4], (2, B, kv_heads, feats, d_head), jnp.float32)
    cz = jax.random.normal(ks[5], (2, B, kv_heads, feats), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = (jnp.einsum("bhgf,bhfd->bhgd", fq, cs[1]),
                jnp.einsum("bhgf,bhf->bhg", fq, cz[1]),
                decay[..., None, None] * cs[1]
                + fk[..., None] * v[..., None, :],
                decay[..., None] * cz[1] + fk)
    idle = np.asarray(cs[1])[~lv]
    num, den, s, z = jax.jit(retention_step.retention_step)(
        fq, fk, v, decay, cs, cz, 1, jnp.asarray(lv))
    _check(np.array_equal(np.asarray(s[1])[~lv], idle), "kernels",
           "retention_step touched an idle row's state")
    return max(_rel_err(np.asarray(g)[lv], np.asarray(w)[lv])
               for g, w in zip((num, den, s[1], z[1]), want))


def phase_kernels(seq: int, long_seq: int, flash_cases: dict,
                  decode_cases: dict, state_cases: dict) -> dict:
    errs = {name: _attention_case(flash_attention, 1, seq, **case)
            for name, case in flash_cases.items()}
    errs.update((name, _decode_case(**case))
                for name, case in decode_cases.items())
    f32 = {name: _state_case(**case) for name, case in state_cases.items()}
    errs.update(f32)
    if long_seq:
        # Nothing forced: full_attention must pick the kernel by itself.
        probe = jax.ShapeDtypeStruct((1, long_seq, 1, 128), jnp.bfloat16)
        _check("pallas_call" in str(jax.make_jaxpr(full_attention)(
            probe, probe, probe)), "kernels",
            f"full_attention did not route to flash at T={long_seq}")
        errs[f"full_attention T={long_seq}"] = _attention_case(
            full_attention, 1, long_seq, 1, 128)
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(384, 512), jnp.float32)
    b = jnp.asarray(rng.randn(512, 256), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = a @ b
    errs["pallas_matmul"] = _rel_err(pallas_matmul(a, b), want)

    # Adasum pair: both kernels against the same formula in numpy f64.
    x, y = (rng.randn(2, 300_000).astype(np.float32) for _ in range(2))
    dot, nx, ny = ((p.astype(np.float64) * q).sum(-1, keepdims=True)
                   for p, q in ((x, y), (x, x), (y, y)))
    want = (1 - dot / (2 * nx)) * x + (1 - dot / (2 * ny)) * y
    errs["adasum pair"] = _rel_err(
        pk.pallas_pair_combine_batched(jnp.asarray(x), jnp.asarray(y)), want)
    f32["adasum pair"] = errs["adasum pair"]
    for name, err in errs.items():
        tol = F32_KERNEL_TOL if name in f32 else MXU_KERNEL_TOL
        _check(err <= tol, "kernels",
               f"{name}: relative error {err:.4g} > {tol}")
    return {name: float(f"{err:.3g}") for name, err in errs.items()}


# --- main ------------------------------------------------------------------

def main() -> int:
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev, n = devices[0], len(devices)
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={n} jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — "
              "nothing was run", file=sys.stderr)
        return 1
    _check(not pk._interpret(), "kernels",
           "Pallas would run interpreted on this backend")
    # Most of what this script compiles takes under the second below
    # which JAX does not keep a program; keep them all, so that a second
    # run compiles nothing.  (TPU only: on the CPU every cache hit logs
    # two lines of XLA's AOT loader.)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter()

    def report(phase: str, rec) -> None:
        print(f"{phase}: {json.dumps(rec)} {json.dumps(meter.take())}",
              flush=True)

    hvd.init()
    _check(hvd.size() == n, "collectives", f"size {hvd.size()} != {n}")
    report("collectives", phase_collectives())
    report("resnet", phase_resnet(**RESNET))

    cfg = TransformerConfig(**LM)
    one_chip = phase_transformer(cfg, LM_BATCH, LM_SEQ, LM_STEPS,
                                 devices[:1], dp=1)
    report("transformer", one_chip)
    if n >= 4:
        report("transformer sharded", phase_transformer_sharded(
            cfg, LM_BATCH, LM_SEQ, LM_STEPS, one_chip))
    report("server", phase_server(cfg, tol=SERVE_LOGIT_TOL, **SERVE))
    report("decode layout", phase_decode_layout(cfg, **DECODE_VIEW))
    report("kernels",
           phase_kernels(KERNEL_SEQ, KERNEL_LONG_SEQ, FLASH_CASES,
                         DECODE_CASES, STATE_CASES))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
