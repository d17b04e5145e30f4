"""Programs of the serving path compiled for a DESCRIBED TPU v5e at the
benchmark's real shapes, on this CPU-only machine: libtpu's compiler is
installed, so what it would do with a program on the chip (which arrays
it copies, how much it keeps in temporaries) is checked here at no chip
time.  Nothing runs, so nothing here is a time or a result.

All of it lives in this one file, and the topology is described inside a
fixture: only one process may load libtpu, and only the worker that is
handed this file does (a second file, or a call made while a module is
imported, would make every other worker fail or skip).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import (
    TransformerConfig,
    init_decode_cache,
    transformer_init,
)


def _benchmark_json(*path):
    """A file of `benchmark/` (configs, traffic), parsed."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", *path)) as f:
        return json.load(f)

# mistral7b_chat_steady's decode view (benchmark/traffic/chat_steady.json,
# benchmark/configs/mistral-7b-serve.json): 32 rows x 3584 slots, pool of
# 3200 pages of 16 tokens, at Mistral-7B's widths.  Two layers: the
# compiler treats every layer of the loop alike, and compiles in 2 s.
ROWS, SLOTS, PAGES, PAGE_TOKENS = 32, 3584, 3200, 16
WIDTHS = dict(vocab_size=32000, d_model=4096, n_heads=32, d_head=128,
              d_ff=14336, n_kv_heads=8, attn_window=4096, n_layers=2,
              compute_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import decode_attention, retention_step

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")    # or the compiler logs to /tmp
    # the decode steps' kernels as the chip would get them, through Mosaic
    mp.setattr(decode_attention, "_interpret", lambda: False)
    mp.setattr(retention_step, "_interpret", lambda: False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


def _leaf_bytes(leaf):
    a = leaf["q"] if isinstance(leaf, dict) else leaf
    return a.size // a.shape[0] * a.dtype.itemsize


def _step_args(one_chip, cfg, rows, slots, quantize=None):
    """(params, cache, tokens) of a served step (vector `pos`), as shapes
    on the described chip."""
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.compute_dtype),
        transformer_init(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(
        lambda: init_decode_cache(cfg, rows, slots, quantize=quantize))
    cache["pos"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((rows,), jnp.int32)
    return one_chip(params), one_chip(cache), one_chip(tokens)


def _prev_ids(one_chip, cfg, rows):
    """The fourth argument of the SERVER's step (`_serve_step_fn`): the
    ids the step before left on the device, which a row fed -1 takes its
    token from."""
    from horovod_tpu.models.decode import serve_ids_len
    return one_chip(jax.ShapeDtypeStruct((serve_ids_len(cfg, rows),),
                                         jnp.int32))


# mistral7b_doc_saturated's view (benchmark/traffic/doc_saturated.json):
# 28 rows x 3840 slots, 7.5 of the kernel's blocks of 512.
DOC_ROWS, DOC_SLOTS = 28, 3840


@pytest.mark.parametrize("rows,slots,quantize,kernels", [
    (ROWS, SLOTS, None, 1), (ROWS, SLOTS, "int8", 0),
    (DOC_ROWS, DOC_SLOTS, None, 1)],
    ids=["bf16", "int8", "doc-28x3840"])
def test_decode_step_reads_the_view_where_it_lies(one_chip, rows, slots,
                                                  quantize, kernels):
    """The served step (vector `pos`): the program's temporaries stay
    far under ONE layer's K slice of the view, so no layer's K or V is
    copied out before it is read (slot-major, PR 27: 235 MB of them;
    a slice of the stack as the kernel's operand: as much) and the
    per-row write does not make the compiler transpose the cache (kv
    heads in the scatter's window: 1.9 GB).  A plain view is read by
    ops/decode_attention.py's kernel, once in the loop over layers, a
    quantized one by the einsum."""
    from horovod_tpu.models.decode import _spec_step_fn

    cfg = TransformerConfig(**WIDTHS)
    args = _step_args(one_chip, cfg, rows, slots, quantize)
    cache = args[1]
    compiled = _spec_step_fn(cfg).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernels
    temp = compiled.memory_analysis().temp_size_in_bytes
    k_slice = _leaf_bytes(cache["k"])
    assert k_slice == rows * 8 * slots * 128 * (1 if quantize else 2)
    assert temp < k_slice // 8, (temp, k_slice)


# brumby14b_longdoc_steady's decode view (benchmark/traffic/
# longdoc_steady.json, benchmark/configs/brumby-14b-serve.json): a state
# a row, 16 rows, the whole vocabulary of 151,936.
STATE_ROWS = 16
STATE_WIDTHS = dict(vocab_size=151936, d_model=5120, n_heads=40, d_head=128,
                    d_ff=17408, n_kv_heads=8, rope_theta=1e6, n_layers=2,
                    compute_dtype=jnp.bfloat16, attn_kind="retention")


@pytest.mark.parametrize("widths,rows,slots,leaf", [
    (WIDTHS, ROWS, SLOTS, "k"), (STATE_WIDTHS, STATE_ROWS, 1, "s")],
    ids=["chat-32x32000", "longdoc-16x151936"])
def test_server_step_picks_its_ids_in_the_one_program(one_chip, widths,
                                                      rows, slots, leaf):
    """The server's own step (`_serve_step_fn`: the decode step with the
    greedy pick in it) is ONE program, named as the benchmark's trace
    readers look for the step, which hands back the logits, `[rows]`
    int32 ids and the cache; the pick costs no temporaries to speak of
    (under the logits' own size) and the cache is still read and
    written where it lies."""
    from horovod_tpu.models.decode import _serve_step_fn, _spec_step_fn

    cfg = TransformerConfig(**widths)
    args = _step_args(one_chip, cfg, rows, slots)
    cache = args[1]
    bare = _spec_step_fn(cfg).lower(*args).compile()
    lowered = _serve_step_fn(cfg).lower(*args, _prev_ids(one_chip, cfg, rows))
    logits, ids, out_cache = lowered.out_info
    assert (logits.shape, logits.dtype) == ((rows, cfg.vocab_size),
                                            jnp.float32)
    assert (ids.shape, ids.dtype) == ((rows,), jnp.int32)
    assert {n: a.shape for n, a in out_cache.items()} == \
        {n: a.shape for n, a in cache.items()}
    picked = lowered.compile()
    assert picked.as_text().startswith("HloModule jit__lambda,")
    temp = picked.memory_analysis().temp_size_in_bytes
    logits_bytes = rows * cfg.vocab_size * 4
    assert temp <= bare.memory_analysis().temp_size_in_bytes + logits_bytes
    assert temp < _leaf_bytes(cache[leaf]) // 8, temp


def test_retention_step_passes_over_its_state_where_it_lies(one_chip):
    """`brumby14b_longdoc_steady`'s served step at the cell's 16 rows and
    8 layers (4.4 GB of states): a layer's pass over the state is ONE
    kernel in the loop over layers (ops/retention_step.py), handed the
    stacked leaves whole and aliased onto them, so the program holds no
    temporary of a layer's state (550 MB; a slice handed to a kernel, or
    a read-out that keeps the decayed state beside the old one, would
    be one) and the whole cache comes back in the argument's buffers."""
    from horovod_tpu.models.decode import _serve_step_fn

    cfg = TransformerConfig(**{**STATE_WIDTHS, "n_layers": 8})
    args = _step_args(one_chip, cfg, STATE_ROWS, 1)
    cache = args[1]
    assert cache["s"].shape == (8, 16, 8, 8320, 128)
    compiled = _serve_step_fn(cfg).lower(
        *args, _prev_ids(one_chip, cfg, STATE_ROWS)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    state = sum(cache[n].size * cache[n].dtype.itemsize for n in "sz")
    assert state == 16 * 8 * 8 * 8320 * 129 * 4
    assert mem.alias_size_in_bytes >= state


# laguna_xs2_codegen_steady's decode view (benchmark/traffic/
# codegen_steady.json, benchmark/configs/laguna-xs2-serve.json): 32 rows,
# the two full layers at 7168 slots, the three sliding layers' rings of
# 512, 256 experts of 2048 x 512 in each of four sparse layers.
PATTERN_ROWS, PATTERN_SLOTS = 32, 7168


def _pattern_cell():
    from benchmark.runners.pattern_serve import transformer_config
    return transformer_config(
        _benchmark_json("configs", "laguna-xs2-serve.json"))


def test_patterned_step_copies_neither_cache_nor_experts(one_chip,
                                                         monkeypatch):
    """The served step of a patterned model with routed experts at the
    cell's shapes: its temporaries stay far under ONE full layer's K
    slice of the view (which the kernel of ops/decode_attention.py reads
    out of the stack, 32 x 7168 slots under groups of 6), so no cache is
    transposed or copied out, and far under one matrix of ONE layer's experts: the experts' stack
    goes to the grouped product whole, where a layer's slice of it would
    be copied first (537 MB a matrix, 4.4 GB of temporaries in all)."""
    from horovod_tpu.models import experts
    from horovod_tpu.models.decode import _serve_step_fn

    monkeypatch.setattr(experts, "_interpret", lambda: False)
    cfg = _pattern_cell()
    args = _step_args(one_chip, cfg, PATTERN_ROWS, PATTERN_SLOTS)
    cache = dict(args[1])
    cache.pop("routed")             # as the server's cache lends it
    lowered = _serve_step_fn(cfg).lower(
        args[0], cache, args[2], _prev_ids(one_chip, cfg, PATTERN_ROWS))
    logits, ids, out_cache = lowered.out_info
    assert ids.shape == (PATTERN_ROWS + 4 * 3,)     # `experts.ROUTED`
    assert out_cache["k"]["sliding_attention"].shape == (3, 32, 8, 512, 128)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__lambda,")
    # twelve grouped products and the two full layers' attention (48
    # query heads on 8: groups of 6); the rings of 512 are the einsum's
    assert text.count('custom_call_target="tpu_custom_call"') == 12 + 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    k_slice = PATTERN_ROWS * 8 * PATTERN_SLOTS * 128 * 2
    one_matrix = 256 * 2048 * 512 * 2
    assert cache["k"]["full_attention"].shape[1:] == (32, 8, 7168, 128)
    assert temp < k_slice // 2, (temp, k_slice)
    assert temp < one_matrix // 2, (temp, one_matrix)


def test_patterned_cells_longest_prefill_takes_its_tiles(one_chip,
                                                         monkeypatch):
    """`laguna_xs2_codegen_steady`'s longest prompt (6144 tokens, into
    the scratch cache of its own pages that boarding builds whatever is
    to come) prefilled at the configuration's widths: the flash kernel at
    the tiles `decode.prompt_tiles` fits to it, 1024 x 1024, is inside the
    v5e's VMEM under 48 heads and under 64 with the window of 512; five such calls under the
    scope `hvd.attn` beside the twelve grouped products, and no
    [.., T, T] array.  Its temporaries fit beside what the cell holds
    (10.3 GB of weights, pool, view and rings: PERF.md 4)."""
    from horovod_tpu.models import decode, experts
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.serve.server import _prefill_fn

    monkeypatch.setattr(experts, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    cfg = _pattern_cell()
    T = 6144                # (the scratch cache is the prompt's pages)
    assert decode.prompt_tiles(T, cfg.d_head) == (T, (1024, 1024))
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.compute_dtype),
        transformer_init(jax.random.PRNGKey(0), cfg)))
    scratch = jax.eval_shape(lambda: init_decode_cache(cfg, 1, T))
    compiled = _prefill_fn(cfg).lower(
        one_chip(params), one_chip(scratch),
        one_chip(jax.ShapeDtypeStruct((1, T), jnp.int32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5 + 12
    assert text.count("hvd.attn") >= 5
    assert f"{T},{T}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)])
def test_every_tile_a_prompt_can_take_fits_the_vmem(one_chip, monkeypatch,
                                                    heads, window):
    """The flash kernel at Laguna's two shapes (8 KV heads of 128,
    bfloat16) at every tile `decode.prompt_tiles` can pick, 128 to 1024
    in steps of 128, two tiles a prompt: Mosaic takes each (a second or
    two a kernel)."""
    from horovod_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    for tile in range(128, 1025, 128):
        q, k = (one_chip(jax.ShapeDtypeStruct((1, 2 * tile, h, 128),
                                              jnp.bfloat16))
                for h in (heads, 8))
        jax.jit(lambda q, k, v: flash_attention.flash_attention(
            q, k, v, causal=True, window=window,
            blocks=(tile, tile))).lower(q, k, k).compile()


def test_pool_write_back_moves_slots_only(one_chip):
    """`scatter_slots`, once a step: the slots' bytes and no transposed
    copy of pool or view (layers or kv heads as window axes around the
    slot: 3.6 GB of temporaries)."""
    from horovod_tpu.serve import pool as P

    cfg = TransformerConfig(**WIDTHS)
    kv = lambda rows, slots: tuple(jax.eval_shape(
        lambda: init_decode_cache(cfg, rows, slots))[n] for n in "kv")
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32)
    compiled = P._scatter_slots_jit.lower(
        one_chip(kv(PAGES, PAGE_TOKENS)), one_chip(kv(ROWS, SLOTS)),
        *(one_chip(idx),) * 4).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_patterned_train_step_fits_beside_its_state(one_chip, monkeypatch):
    """`lfm2_8b_train_8k`'s train step (benchmark/configs/
    lfm2-8b-a1b-train.json at B 4 x 8192, through `make_train_step`): the
    compiler takes it, its temporaries fit beside the 6.1 GB of
    parameters and AdamW's moments, and they are less than ONE sequence's
    float32 scores of one layer ([32, 8192, 8192]: 8.6 GB), and no
    [.., T, T] array is in the program, forward or backward (attention is
    the flash kernel's calls), and less than the logits of the batch and
    their gradient (the loss is taken a sequence at a time).  The routed
    experts' products are the grouped kernels: three forward, three
    recomputed and six backward a sparse layer; the sorted rows between
    them are worked in loops (`experts._worked`), seven a sparse layer.
    A loop's result cannot take an operand's place as a fusion's can, and
    the scheduler runs loops late and their zeros early: 7.98 GB of
    temporaries where the step without loops asked for 6.94 (9.06 with
    one more mask in the layer: PERF.md 6, PR 41)."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.runners.conv_moe_train import transformer_config
    from horovod_tpu.models import experts, make_train_step
    from horovod_tpu.ops import flash_attention

    monkeypatch.setattr(experts, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    m = _benchmark_json("configs", "lfm2-8b-a1b-train.json")
    tr = _benchmark_json("traffic", "seq8k_b4.json")
    cfg = transformer_config(m, jnp.bfloat16)
    dev, = one_chip(jax.ShapeDtypeStruct((), jnp.int32)).sharding.device_set
    mesh = Mesh(np.array([dev]), ("dp",))
    on = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, P())),
        tree)
    hp = m["train"]["optimizer"]
    opt = optax.adamw(hp["learning_rate"], b1=hp["b1"], b2=hp["b2"],
                      eps=hp["eps"], weight_decay=hp["weight_decay"])
    step, _, _ = make_train_step(mesh, cfg, opt)
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 507820288
    B, T = tr["per_chip_batch"], tr["seq_len"]
    toks = on(jax.ShapeDtypeStruct((B, T), jnp.int32))
    compiled = step.lower(on(params), on(jax.eval_shape(opt.init, params)),
                          (toks, toks)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6.09e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    scores = 32 * T * T * 4
    logits = B * T * m["vocab_size"] * 4
    assert mem.temp_size_in_bytes < scores
    assert mem.temp_size_in_bytes < 6.1e9 + 2 * logits
    text = compiled.as_text()
    assert f"{T},{T}]" not in text
    # seven loops over the sorted rows a sparse layer, each ONE loop: the
    # gather and `up * silu(gate)` forward and recomputed, three backward
    pairs = B * T * m["num_experts_per_tok"]
    assert sum(" while(" in line and f"[{pairs}," in line
               for line in text.splitlines()) == 7 * 4
    assert text.count("tgmm") and text.count("hvd.moe.experts")
    assert text.count("hvd.conv") and text.count("hvd.attn")


def _latent_cell():
    from benchmark.runners.latent_serve import transformer_config
    m = _benchmark_json("configs", "gigachat3.1-702b-a36b-serve.json")
    sv = _benchmark_json("traffic", "longctx_steady.json")["server"]
    return m, transformer_config(m), sv


#: a v5e's memory as the runtime gives it (`bytes_limit`)
CHIP_BYTES = 16909336064


def _latent_held(m, cfg, sv):
    """Bytes `gigachat702b_longctx_steady` holds whatever runs: the
    weights, the pool's pages and the one decode view."""
    from benchmark.lib import counts_latent
    token = cfg.n_layers * (512 + 128) * 2      # the key in whole tiles
    return (2 * counts_latent.param_count(m)
            + sv["pool_pages"] * m["serve"]["page_tokens"] * token
            + sv["max_batch"] * sv["max_seq_tokens"] * token)


def test_latent_cells_step_fits_and_reads_where_it_lies(one_chip,
                                                        monkeypatch):
    """`gigachat702b_longctx_steady`'s decode step (benchmark/configs/
    gigachat3.1-702b-a36b-serve.json at the traffic's rows and slots):
    five reads of the latent cache by ops/decode_attention.py's kernel
    and twelve grouped products over the 16 held experts (a contraction
    of 7168 in tiles: whole, its weight tiles pass the chip's VMEM); its
    temporaries stay far under one layer's slice of the view, so no
    layer's latents are copied out before they are read."""
    from horovod_tpu.models import experts
    from horovod_tpu.models.decode import _serve_step_fn

    monkeypatch.setattr(experts, "_interpret", lambda: False)
    m, cfg, sv = _latent_cell()
    rows, slots = sv["max_batch"], sv["max_seq_tokens"]
    args = _step_args(one_chip, cfg, rows, slots)
    cache = dict(args[1])
    cache.pop("routed")             # as the server's cache lends it
    assert cache["k"]["latent"].shape == (5, rows, 1, slots, 512)
    assert cache["v"]["latent"].shape == (5, rows, 1, slots, 128)
    lowered = _serve_step_fn(cfg).lower(
        args[0], cache, args[2], _prev_ids(one_chip, cfg, rows))
    assert lowered.out_info[1].shape == (rows + 4 * 3,)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5 + 12
    assert text.count("hvd.attn.latent") and text.count("hvd.moe.experts")
    mem = compiled.memory_analysis()
    layer_slice = rows * slots * 512 * 2
    assert mem.temp_size_in_bytes < layer_slice // 2, mem.temp_size_in_bytes
    held = _latent_held(m, cfg, sv)
    assert 0.75 * CHIP_BYTES < held < 0.80 * CHIP_BYTES
    assert held + mem.temp_size_in_bytes < CHIP_BYTES


def test_latent_cells_longest_prefill_fits_beside_the_cache(one_chip,
                                                            monkeypatch):
    """The cell's 16384-token prompt, prefilled whole in the expanded
    form (64 heads of 192 through the flash kernel in groups, the dense
    MLP and the experts in passes of tokens, the residual stream
    written): its temporaries fit beside the weights, the pool and the
    view with room to spare, and no [.., T, T] array is in the program.
    Left to itself (every head at once, every token at once, the
    residual stream's addends kept) the program asked for 6.1 GB."""
    from horovod_tpu.models import experts
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.serve.server import _prefill_fn

    monkeypatch.setattr(experts, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    m, cfg, sv = _latent_cell()
    T = 16384               # (the scratch cache is the prompt's pages)
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(cfg.compute_dtype),
        transformer_init(jax.random.PRNGKey(0), cfg)))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 4176338944                  # PERF.md 4: 4.176 B, 8.35 GB
    scratch = jax.eval_shape(lambda: init_decode_cache(cfg, 1, T))
    compiled = _prefill_fn(cfg).lower(
        one_chip(params), one_chip(scratch),
        one_chip(jax.ShapeDtypeStruct((1, T), jnp.int32))).compile()
    mem = compiled.memory_analysis()
    assert f"{T},{T}]" not in compiled.as_text()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
    assert _latent_held(m, cfg, sv) + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < CHIP_BYTES


# Lowered text of the programs that ops/retention_step.py's PR (39) must
# not move, hashed at its parent (6635e6d) with `_programs` below: the
# softmax decode step with the kernel over live blocks and with the
# einsum, a Mistral prefill, a patterned model's step with routed experts
# (Laguna's kind), the uniform train step, and a retention model's
# PREFILL.  A change of JAX moves them all.
PARENT_TEXT = {
    "mistral_step_kernel": "73c1243f07b3a6dc",
    "mistral_step_einsum": "583b21132fb4cd5f",
    "mistral_prefill": "209877bf1d709583",
    # (PR 42: the expert layer counts a third number a sparse layer,
    # `experts.ROUTED`'s `pairs_here`; the five others stand)
    "laguna_step": "ccd0beee2ae71c14",
    "mistral_train": "cc76927979bf3144",
    "brumby_prefill": "fc1050497b5397b8",
}


def _programs():
    import optax

    from horovod_tpu.models import (make_train_step, transformer_decode_step,
                                    transformer_prefill)
    from horovod_tpu.models.transformer import AttnSpec
    from horovod_tpu.parallel.mesh import create_hybrid_mesh

    tiny = dict(vocab_size=64, d_model=32, n_heads=4, d_head=8, d_ff=64,
                n_layers=2, n_kv_heads=2)
    ids = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    weights = lambda cfg: jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), cfg))

    def step(cfg, rows, slots):
        cache = jax.eval_shape(lambda: init_decode_cache(cfg, rows, slots))
        cache["pos"] = ids(rows)
        return jax.jit(
            lambda p, c, t: transformer_decode_step(p, c, t, cfg)).lower(
                weights(cfg), cache, ids(rows))

    def prefill(cfg, n, slots):
        return jax.jit(
            lambda p, c, t: transformer_prefill(p, c, t, cfg)).lower(
                weights(cfg),
                jax.eval_shape(lambda: init_decode_cache(cfg, 1, slots)),
                ids(1, n))

    def train(cfg):
        opt = optax.adamw(3e-4)
        fn = make_train_step(
            create_hybrid_mesh(devices=jax.devices()[:1], dp=1), cfg, opt)[0]
        p = weights(cfg)
        return fn.lower(p, jax.eval_shape(opt.init, p),
                        (ids(2, 16), ids(2, 16)))

    mistral = TransformerConfig(**tiny, attn_window=2048)
    laguna = TransformerConfig(
        **tiny, layer_attn=("full", "full"), layer_mlp=("dense", "experts"),
        attn_specs=(("full", AttnSpec(4)),), n_experts=8,
        experts_per_token=2, expert_ff=16, shared_ff=16, routed_scale=2.5,
        attn_gate=True)
    return {
        "mistral_step_kernel": lambda: step(mistral, 3, 1024),
        "mistral_step_einsum": lambda: step(mistral, 3, 64),
        "mistral_prefill": lambda: prefill(mistral, 200, 256),
        "laguna_step": lambda: step(laguna, 2, 16),
        "mistral_train": lambda: train(
            TransformerConfig(**tiny, attn_window=16)),
        "brumby_prefill": lambda: prefill(
            TransformerConfig(**tiny, attn_kind="retention"), 300, 1),
    }


@pytest.mark.parametrize("name", list(PARENT_TEXT))
def test_untouched_programs_lower_to_the_parents_text(name, monkeypatch):
    """None of the six other cells runs `_retention_decode_layer`: their
    step, prefill and train programs lower (here, for the CPU, at tiny
    widths) to the text they had at the parent commit."""
    import hashlib

    from horovod_tpu.ops import decode_attention

    # for the CPU, whatever `one_chip` holds for the tests above
    monkeypatch.setattr(decode_attention, "_interpret", lambda: True)
    text = _programs()[name]().as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_TEXT[name]
