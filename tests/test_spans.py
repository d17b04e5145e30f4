"""The program's spans on the profiler's clock (`utils/timeline.span`):
the primitive alone, then a tiny server run inside a real `jax.profiler`
session and read back with `jax.profiler.ProfileData`, the same server
with `HOROVOD_TIMELINE` on as well, and with neither on."""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import (TransformerConfig, transformer_generate,
                                transformer_init)
from horovod_tpu.models import experts as experts_mod
from horovod_tpu.serve import InferenceServer
from horovod_tpu.trace import core as trace_core
from horovod_tpu.utils import timeline as tl_mod
from horovod_tpu.utils.timeline import span, start_timeline, stop_timeline

PHASES = ("admit", "sample", "launch", "fetch", "observe")
INSIDE_LAUNCH = ("put", "write_through")
# What a plain step's `launch` says of its work whatever the cache, what
# each kind of cache adds, and what `observe` says after the iteration's
# sync, which is of the step dispatched the iteration BEFORE: one step is
# kept in flight.
WORK = {"dstep", "rows", "rows_pct", "live_tokens", "ahead"}
CACHE_SAYS = {"paged": {"view_read_pct"},
              "windowed": {"view_read_pct", "ring_tokens"},
              "retention": {"state_read_pct"},
              "latent": {"view_read_pct"}}
ROUTING = ("windowed", "latent")   # the kinds whose model routes experts
COUNTS = {"step", "rows", "admitted", "finished", "decided"}
ROUTED = {"experts_hit", "pairs_here"}
WINDOW = 4                         # of the patterned model's ring layers
OUTPUTS = (2, 4, 3, 5, 2)          # tokens asked of the five requests
# What the parent of the PR that added the spans gives on this traffic
# (max_batch 2, fifo): the spans may move neither.
PARENT_DEVICE_STEPS, PARENT_STEPS = 8, 9


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=2, compute_dtype=jnp.float32)
    return cfg, transformer_init(jax.random.PRNGKey(0), cfg)


def _serve(model, after_step=None, cls=InferenceServer, **kw):
    """Five requests through a two-row server; -> (server, prompts by
    request, generated tokens by request).  `after_step(server)` is
    called between steps, where a benchmark's runner looks."""
    cfg, params = model
    srv = cls(params, cfg, max_seq_tokens=24, max_batch=2, page_tokens=4,
              **kw)
    rng = np.random.RandomState(2)
    prompts = {}
    for n in OUTPUTS:
        prompt = rng.randint(0, 64, size=4)
        prompts[srv.submit(prompt.tolist(), n)] = prompt
    if after_step is None:
        done = srv.run()
    else:
        done = []
        while not srv.sched.drained():
            done.extend(srv.step())
            after_step(srv)
    return srv, prompts, {s.req.req_id: list(s.generated) for s in done}


def _profiled(tmp_path, fn):
    """Run `fn` inside a profiler session; -> (its result, the `hvd.*`
    events of the host plane as (name, start_ns, end_ns, stats))."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           {k: v for k, v in ev.stats})
                          for ev in line.events if ev.name.startswith("hvd.")]
    return result, sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One server run with a profiler session and a timeline both on."""
    tmp = tmp_path_factory.mktemp("spans")
    tlf = str(tmp / "timeline.json")
    start_timeline(tlf)
    try:
        (srv, prompts, tokens), spans = _profiled(
            tmp / "prof", lambda: _serve(model))
    finally:
        stop_timeline()
    return dict(srv=srv, prompts=prompts, tokens=tokens, spans=spans,
                events=trace_core.load_events(tlf))


def _inside(spans, outer, name=None):
    return [s for s in spans if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]
            and (name is None or s[0] == name)]


def _steps(spans):
    return [s for s in spans if s[0] == "hvd.serve.step"]


# -- the primitive -------------------------------------------------------

def test_span_off_reads_no_clock_and_passes_exceptions(monkeypatch):
    assert tl_mod.get_timeline() is None

    def no_clock():
        raise AssertionError("a span read the clock with nothing on")
    monkeypatch.setattr(tl_mod.time, "perf_counter", no_clock)
    with span("step", "serve", {"step": 1}):
        with span("admit", "serve"):
            pass
    with pytest.raises(KeyError):
        with span("admit", "serve"):
            raise KeyError("through")


def test_span_writes_the_timeline_event(tmp_path):
    tlf = str(tmp_path / "tl.json")
    start_timeline(tlf)
    try:
        with span("outer", "unit", {"n": 3}, tid="lane/7"):
            with span("inner", "unit"):
                pass
    finally:
        stop_timeline()
    evs = {e["name"]: e for e in trace_core.load_events(tlf)}
    outer, inner = evs["outer"], evs["inner"]
    assert (outer["ph"], outer["cat"], outer["tid"], outer["args"]) == \
        ("X", "unit", "lane/7", {"n": 3})
    assert (inner["cat"], inner["tid"]) == ("unit", "unit")
    assert "args" not in inner
    # the parent is the enclosing span: the child lies inside it
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.2


def test_span_in_a_profiler_session_carries_its_arguments(tmp_path):
    def body():
        with span("step", "unit", {"step": 7, "rows": 28}):
            with span("child", "unit"):
                pass
    _, spans = _profiled(tmp_path, body)
    by = {s[0]: s for s in spans}
    assert by["hvd.unit.step"][3] == {"step": 7, "rows": 28}
    assert by["hvd.unit.child"][3] == {}
    assert _inside(spans, by["hvd.unit.step"]) == [by["hvd.unit.child"]]


def test_trace_annotation_only_in_the_primitive():
    """The program writes into the profiler's trace in one place."""
    root = os.path.dirname(hvd.__file__)
    users = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            if "TraceAnnotation" in f.read():
                users.append(os.path.relpath(path, root))
    assert users == [os.path.join("utils", "timeline.py")]


# -- the server's step ---------------------------------------------------

def test_step_and_observe_carry_the_steps_counts(traced):
    steps = _steps(traced["spans"])
    assert [s[3]["step"] for s in steps] == list(range(PARENT_STEPS))
    finished = 0
    for s in steps:
        assert set(s[3]) == {"step", "queued", "active"}
        obs, = _inside(traced["spans"], s, "hvd.serve.observe")
        # a step that made a sync says of which device step; this model
        # routes nothing, so nothing of experts
        synced = bool(_inside(traced["spans"], s, "hvd.serve.fetch"))
        assert set(obs[3]) == COUNTS | ({"dstep"} if synced else set())
        assert obs[3]["step"] == s[3]["step"]
        prefills = _inside(traced["spans"], s, "hvd.serve.prefill")
        assert obs[3]["admitted"] == len(prefills)
        # rows on entry, plus boarded, less ended = rows decoded
        assert obs[3]["rows"] == (s[3]["active"] + obs[3]["admitted"]
                                  - obs[3]["finished"])
        finished += obs[3]["finished"]
    assert steps[0][3] == {"step": 0, "queued": len(OUTPUTS), "active": 0}
    assert finished == len(OUTPUTS)


def test_phases_nest_without_overlap_and_cover_the_step(traced):
    covered = total = 0
    in_flight = False       # a step dispatched and not fetched, on entry
    for s in _steps(traced["spans"]):
        launches = _inside(traced["spans"], s, "hvd.serve.launch")
        held = [k for l in launches for k in _inside(traced["spans"], l)]
        kids = [k for k in _inside(traced["spans"], s)
                if k[0] != "hvd.serve.prefill" and k not in held]
        names = [k[0].rsplit(".", 1)[1] for k in kids]
        rows = _inside(traced["spans"], s, "hvd.serve.observe")[0][3]["rows"]
        # in this order: a step that decodes launches, and BEHIND the
        # launch fetches the ids of the step the iteration before
        # launched; the first after a drain has none to fetch; one that
        # only retires its last rows launches nothing and only fetches
        assert names == [p for p in PHASES
                         if (p != "launch" or rows)
                         and (p != "fetch" or in_flight)]
        in_flight = bool(rows)
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]
        # the host's two parts of a launch lie inside it, one after the
        # other; what is left of it is the dispatch
        assert [k[0].rsplit(".", 1)[1] for k in held] == \
            (list(INSIDE_LAUNCH) if rows else [])
        for a, b in zip(held, held[1:]):
            assert a[2] <= b[1]
        covered += sum(k[2] - k[1] for k in kids)
        total += s[2] - s[1]
    assert not in_flight                    # the drain landed the last
    assert covered >= 0.95 * total
    # nothing of the server lies outside a step
    in_steps = sum(len(_inside(traced["spans"], s)) + 1
                   for s in _steps(traced["spans"]))
    assert in_steps == len(traced["spans"])


def test_one_prefill_span_per_admitted_request(traced):
    prefills = [s for s in traced["spans"] if s[0] == "hvd.serve.prefill"]
    assert sorted(p[3]["req"] for p in prefills) == sorted(traced["prompts"])
    for p in prefills:
        assert set(p[3]) == {"req", "prompt_tokens", "row", "pages",
                             "scratch_pages", "queue_wait_us"}
        assert p[3]["prompt_tokens"] == 4
        assert p[3]["pages"] == -(-(4 + OUTPUTS[p[3]["req"]]) // 4)
        assert p[3]["scratch_pages"] == 1       # the prompt's, 4 tokens
        assert p[3]["queue_wait_us"] >= 0
        # a child of exactly one step's admit
        assert len([a for a in traced["spans"] if a[0] == "hvd.serve.admit"
                    and a[1] <= p[1] and p[2] <= a[2]]) == 1
    # requests that waited for a row waited longer than the first two
    wait = {p[3]["req"]: p[3]["queue_wait_us"] for p in prefills}
    assert wait[4] > wait[0]


def test_timeline_prefill_event_keeps_its_shape(traced):
    """With HOROVOD_TIMELINE on as well, the `prefill` event is what it
    was (name, category, lane, today's keys) with the new keys beside."""
    prefills = [e for e in traced["events"] if e["name"] == "prefill"]
    assert len(prefills) == len(OUTPUTS)
    for e in prefills:
        rid = e["args"]["req"]
        assert (e["ph"], e["cat"], e["tid"]) == ("X", "serve", f"req/{rid}")
        assert {"req", "prompt_tokens", "row"} <= set(e["args"])
        assert {"pages", "scratch_pages", "queue_wait_us"} <= set(e["args"])
    # the step's phases are there too, on the category's own lane
    names = {e["name"] for e in traced["events"]
             if e.get("cat") == "serve" and e.get("tid") == "serve"}
    assert {"step", *PHASES} <= names
    report = trace_core.analyze_serve({0: traced["events"]}, align="wall")
    assert report["summary"]["completed"] == len(OUTPUTS)


def test_fetch_carries_the_bytes_of_the_steps_one_sync(traced):
    """A plain step is synced on once, an iteration after its launch:
    on its `[max_batch]` int32 ids (max_batch 2); the whole logits come
    to the host only for who reads `last_logits`, which `logit_fetches`
    counts."""
    fetches = [s for s in traced["spans"] if s[0] == "hvd.serve.fetch"]
    assert len(fetches) == traced["srv"].device_steps
    assert all(f[3] == {"bytes": 4 * 2} for f in fetches)
    fetch_events = [e for e in traced["events"] if e["name"] == "fetch"]
    assert [e["args"] for e in fetch_events] == [{"bytes": 8}] * len(fetches)
    srv = traced["srv"]
    assert srv.logit_fetches == 0
    srv.last_logits, srv.last_logits
    assert srv.logit_fetches == 1           # kept until the next step


def test_launch_carries_the_view_read_share(model, traced, tmp_path):
    """`hvd.serve.launch` says which share of the view's blocks the step's
    attention reads (`view_read_pct`, from `row_pos` on the host): all of
    a view of one block or less, which the einsum reads whole; a
    retention server keeps no slots and says nothing of a view (what it
    says instead: `test_retention_launch_carries_the_state_read_share`)."""
    launches = [s for s in traced["spans"] if s[0] == "hvd.serve.launch"]
    assert len(launches) == traced["srv"].device_steps
    assert all(set(s[3]) == WORK | CACHE_SAYS["paged"] for s in launches)
    assert all(s[3]["view_read_pct"] == 100.0 for s in launches)
    events = [e for e in traced["events"] if e["name"] == "launch"]
    assert [e["args"] for e in events] == [s[3] for s in launches]
    cfg, _ = model
    rcfg = dataclasses.replace(cfg, attn_kind="retention", n_kv_heads=2)
    (srv, _, _), spans = _profiled(tmp_path, lambda: _serve(
        (rcfg, transformer_init(jax.random.PRNGKey(0), rcfg))))
    launches = [s for s in spans if s[0] == "hvd.serve.launch"]
    assert len(launches) == srv.device_steps > 0
    assert all("view_read_pct" not in s[3] for s in launches)


@pytest.mark.parametrize("d_head,kernel", [(8, False), (128, True)],
                         ids=["einsums", "kernel"])
def test_retention_launch_carries_the_state_read_share(model, tmp_path,
                                                       d_head, kernel):
    """A retention server's `hvd.serve.launch` says which share of the
    rows' states the step reads (`state_read_pct`, from `row_pos` on the
    host) and nothing of a view: every row's, 100, where the einsums
    make the pass (a state too small to tile), the live rows over the
    rows where the kernel of ops/retention_step.py does, so that over a
    run the shares sum to the occupancy.  The tokens are what
    `transformer_generate` gives each request alone either way."""
    cfg, _ = model
    rcfg = dataclasses.replace(cfg, attn_kind="retention", n_kv_heads=2,
                               d_head=d_head)
    params = transformer_init(jax.random.PRNGKey(0), rcfg)
    (srv, prompts, tokens), spans = _profiled(
        tmp_path, lambda: _serve((rcfg, params)))
    assert srv.pool.kernel == kernel
    launches = [s for s in spans if s[0] == "hvd.serve.launch"]
    assert len(launches) == srv.device_steps > 0
    assert all(set(s[3]) == WORK | CACHE_SAYS["retention"]
               for s in launches)
    shares = [s[3]["state_read_pct"] for s in launches]
    if kernel:
        assert set(shares) == {50.0, 100.0}     # one row live, or both
        assert sum(shares) / 100 == pytest.approx(srv.occupancy_sum)
    else:
        assert set(shares) == {100.0}
    for rid, prompt in prompts.items():
        want, _ = transformer_generate(params, rcfg,
                                       jnp.asarray(prompt)[None],
                                       OUTPUTS[rid])
        assert tokens[rid] == np.asarray(want)[0].tolist()


@pytest.mark.parametrize("kw,positions,want", [
    # three blocks of 512 a row: 0 + 1 + 2 + 3 of 4 x 3
    (dict(view_pages=96), [0, 5, 600, 1535], 50.0),
    (dict(view_pages=96), [0, 0, 0, 0], 0.0),
    (dict(view_pages=96), [4000, 1536, 1, 511], 100.0 * 8 / 12),
    (dict(view_pages=60), [0, 5, 600, 900], 100.0),     # under two blocks
    (dict(view_pages=96, quantize="int8"), [0, 5, 600, 1535], 100.0),
], ids=["ragged", "idle", "wrapped", "einsum_small", "einsum_quantized"])
def test_paged_cache_reckons_the_blocks_a_step_reads(model, kw, positions,
                                                      want):
    """`PagedKVPool.view_read_pct`: the kernel's block count where the
    shape rule of models/decode.py picks the kernel, 100 where it picks
    the einsum; on the host, no array of the device touched."""
    from horovod_tpu.serve.pool import PagedKVPool
    pool = PagedKVPool(model[0], 8, 16, rows=4, **kw)
    assert pool.view_read_pct(np.asarray(positions)) == pytest.approx(want)


def _patterned_cfg():
    from horovod_tpu.models.transformer import AttnSpec
    return TransformerConfig(
        vocab_size=64, d_model=32, d_head=8, d_ff=64, n_layers=3,
        n_kv_heads=2, compute_dtype=jnp.float32,
        layer_attn=("full", "sliding", "full"),
        layer_mlp=("dense", "experts", "experts"),
        attn_specs=(("full", AttnSpec(4)), ("sliding", AttnSpec(6, WINDOW))),
        attn_gate=True, n_experts=8, experts_per_token=2, expert_ff=16,
        shared_ff=16, routed_scale=2.5)


def _latent_cfg():
    """Three latent layers (models/decode.py, "Latent attention"), the
    experts in 4 groups of which 2 are kept, half of group 0 held."""
    from horovod_tpu.models.transformer import LatentSpec, Rotary
    spec = LatentSpec(n_heads=4, q_rank=24, kv_rank=16, nope_dim=8,
                      rope_dim=4, v_dim=12,
                      rotary=Rotary(theta=1e5, yarn_factor=8,
                                    yarn_original=8), scale_factor=1.5)
    return TransformerConfig(
        vocab_size=64, d_model=32, d_head=12, d_ff=64, n_layers=3,
        compute_dtype=jnp.float32, layer_attn=("latent",) * 3,
        layer_mlp=("dense", "experts", "experts"),
        attn_specs=(("latent", spec),), n_experts=16, experts_per_token=4,
        expert_ff=16, shared_ff=16, routed_scale=2.5, experts_held=(0, 2),
        expert_bias=True, route_eps=1e-20, route_groups=4,
        route_groups_kept=2)


def test_patterned_model_counts_its_routing_in_the_one_sync(tmp_path):
    """A model with routed experts: the step's one sync brings the ids
    and, behind them, three counts a sparse layer (`fetch` says how many
    bytes), from which the server keeps `experts_hit_sum`,
    `expert_load_max_sum`, `pairs_here_sum` and `moe_layer_steps`; `prefill` keeps its
    arguments, `pages` counting the full layers' pages; a uniform model
    counts nothing."""
    cfg = _patterned_cfg()
    (srv, prompts, tokens), spans = _profiled(
        tmp_path, lambda: _serve((cfg, transformer_init(
            jax.random.PRNGKey(1), cfg))))
    assert {r: len(t) for r, t in tokens.items()} == dict(enumerate(OUTPUTS))
    fetches = [s for s in spans if s[0] == "hvd.serve.fetch"]
    assert len(fetches) == srv.device_steps
    # [max_batch] ids and `experts.ROUTED` of 2 sparse layers
    assert len(experts_mod.ROUTED) == 3
    assert all(f[3] == {"bytes": 4 * (2 + 2 * 3)} for f in fetches)
    assert srv.moe_layer_steps == 2 * srv.device_steps
    rows = srv.occupancy_sum * 2              # active rows, summed
    assert 2 * srv.moe_layer_steps <= srv.experts_hit_sum <= 2 * 2 * rows
    assert srv.moe_layer_steps <= srv.expert_load_max_sum <= 2 * rows
    # every expert is held: every pair a stepped row routes lies here
    assert srv.pairs_here_sum == 2 * 2 * rows
    assert srv.logit_fetches == 0
    # the pages' view answers for the view (24 slots: the einsum's)
    assert all(s[3]["view_read_pct"] == 100.0 for s in spans
               if s[0] == "hvd.serve.launch")
    for p in (s for s in spans if s[0] == "hvd.serve.prefill"):
        assert set(p[3]) == {"req", "prompt_tokens", "row", "pages",
                             "scratch_pages", "queue_wait_us"}
        assert p[3]["pages"] == -(-(4 + OUTPUTS[p[3]["req"]]) // 4)
        assert p[3]["scratch_pages"] == 1


def test_uniform_model_counts_no_routing(traced):
    srv = traced["srv"]
    assert (srv.experts_hit_sum, srv.expert_load_max_sum,
            srv.pairs_here_sum, srv.moe_layer_steps) == (0, 0, 0, 0)


# -- a step's work, on its own spans, whatever the cache -------------------

@pytest.fixture(scope="module", params=sorted(CACHE_SAYS))
def worked(request, model, tmp_path_factory):
    """A server of each kind of cache run inside a profiler session,
    with what a runner would sum between its steps from `sched.active`
    (each row's `pos`, and `min(pos, window)`) kept beside."""
    kind = request.param
    cfg, params = model
    if kind == "windowed":
        cfg = _patterned_cfg()
    elif kind == "latent":
        cfg = _latent_cfg()
    elif kind == "retention":
        cfg = dataclasses.replace(cfg, attn_kind="retention", n_kv_heads=2)
    if kind != "paged":
        params = transformer_init(jax.random.PRNGKey(1), cfg)
    summed = {"live_tokens": 0, "ring_tokens": 0}

    def runner_sums(srv):
        for seq in srv.sched.active.values():
            summed["live_tokens"] += seq.pos
            summed["ring_tokens"] += min(seq.pos, WINDOW)

    (srv, prompts, tokens), spans = _profiled(
        tmp_path_factory.mktemp(kind),
        lambda: _serve((cfg, params), after_step=runner_sums))
    return dict(kind=kind, model=(cfg, params), srv=srv, tokens=tokens,
                spans=spans, summed=summed)


def _named(spans, name):
    return [s for s in spans if s[0] == "hvd.serve." + name]


def test_launch_and_observe_say_what_the_cache_can(worked):
    """`launch` of a plain step: the step's ordinal, its rows and live
    tokens, whether the step before was still in flight, and from the
    cache its own share read, `ring_tokens` from a cache that keeps
    rings and from no other.  `observe`: the rows of the step this
    iteration launched, and of the step whose ids it FETCHED, the one
    launched an iteration before, the ordinal and, from a model that
    routes, that step's routing."""
    kind, spans = worked["kind"], worked["spans"]
    launches, observes = _named(spans, "launch"), _named(spans, "observe")
    assert launches
    for s in launches:
        assert set(s[3]) == WORK | CACHE_SAYS[kind]
        assert s[3]["rows_pct"] == 100.0 * s[3]["rows"] / 2
    assert [o[3]["rows"] for o in observes if o[3]["rows"]] == \
        [s[3]["rows"] for s in launches]
    synced = [o for o in observes if "dstep" in o[3]]
    for o in observes:
        assert set(o[3]) == COUNTS | (
            set() if o not in synced
            else {"dstep"} | ROUTED if kind in ROUTING else {"dstep"})
    # every step is synced on once, in the order of the launches
    assert [o[3]["dstep"] for o in synced] == \
        [s[3]["dstep"] for s in launches]
    # and not by the iteration that launched it: by the next, which
    # launches its own step (if it has a row to step) BEFORE it fetches
    steps = _steps(spans)
    for step, after in zip(steps, steps[1:] + [None]):
        launch = _inside(spans, step, "hvd.serve.launch")
        fetch = _inside(spans, step, "hvd.serve.fetch")
        obs, = _inside(spans, step, "hvd.serve.observe")
        assert len(launch) <= 1 and len(fetch) <= 1
        if launch and fetch:
            assert launch[0][2] <= fetch[0][1]
            assert launch[0][3]["ahead"] == 1
            assert obs[3]["dstep"] == launch[0][3]["dstep"] - 1
        elif launch:
            assert launch[0][3]["ahead"] == 0 and "dstep" not in obs[3]
        if launch:
            fetched_by, = _inside(spans, after, "hvd.serve.observe")
            assert fetched_by[3]["dstep"] == launch[0][3]["dstep"]


class LandsEveryStep(InferenceServer):
    """The order the server had before it kept a step in flight: a
    step's ids are waited for as soon as it is dispatched, and every row
    is fed from the host."""

    def _plain_step(self, rows, feed):
        super()._plain_step(rows, feed)
        self._land()


def test_a_step_in_flight_moves_no_token_of_any_cache(worked):
    """Five requests through two rows, so that rows board in mid-flight
    and every row is re-used, a step kept in flight throughout: token
    for token, step for step and count for count what the server gives
    that lands every step.  (Against `transformer_generate`: the paged
    and the state cache in test_serve.py and above, the patterned in
    test_pattern_moe.py, the latent in test_latent.py.)"""
    srv = worked["srv"]
    landed, _, tokens = _serve(worked["model"], cls=LandsEveryStep)
    assert tokens == worked["tokens"]
    for name in ("device_steps", "step_no", "occupancy_sum", "tokens_out",
                 "experts_hit_sum", "expert_load_max_sum", "pairs_here_sum",
                 "moe_layer_steps", "rows_dropped"):
        assert getattr(srv, name) == getattr(landed, name)
    assert landed.steps_ahead == 0 < srv.steps_ahead


def test_sums_of_the_arguments_are_the_servers_counters(worked):
    """Over a whole run, to the last digit: the work the spans carry is
    what the server counted and what a runner would have summed."""
    srv, spans, kind = worked["srv"], worked["spans"], worked["kind"]
    launches, observes = _named(spans, "launch"), _named(spans, "observe")
    assert [s[3]["dstep"] for s in launches] == list(range(srv.device_steps))
    assert sum(s[3]["rows"] for s in launches) == srv.occupancy_sum * 2
    assert sum(s[3]["rows_pct"] for s in launches) == \
        100 * srv.occupancy_sum
    assert sum(s[3]["ahead"] for s in launches) == srv.steps_ahead
    # every step but the first of a batch: this traffic never drains
    assert srv.steps_ahead == srv.device_steps - 1
    assert sum(s[3]["live_tokens"] for s in launches) == \
        worked["summed"]["live_tokens"]
    assert sum(o[3].get("experts_hit", 0) for o in observes) == \
        srv.experts_hit_sum
    assert sum(o[3].get("pairs_here", 0) for o in observes) == \
        srv.pairs_here_sum
    assert (srv.experts_hit_sum > 0) == (kind in ROUTING)
    # a latent server holds 2 of 16 experts: fewer pairs than the rows
    # routed, and the gauge of its cache under its own label
    if kind == "latent":
        routed = 4 * 2 * srv.occupancy_sum * 2   # k x sparse layers x rows
        assert 0 < srv.pairs_here_sum < routed
    if kind == "windowed":
        assert sum(s[3]["ring_tokens"] for s in launches) == \
            worked["summed"]["ring_tokens"]
        # the rings have wrapped: the two sums differ
        assert worked["summed"]["ring_tokens"] < \
            worked["summed"]["live_tokens"]


def test_put_and_write_through_lie_inside_launch(worked):
    """The host's two parts of a launch, for every cache (a retention
    server carries nothing to anywhere: its `write_through` opens all
    the same, around nothing), in order and apart; nothing else lies in
    a launch, not the fetch either: a launch is followed by the fetch of
    the step BEFORE its own, and its own step's comes behind the next
    launch (or, the last one's, alone in the draining iteration)."""
    spans = worked["spans"]
    launches = _named(spans, "launch")
    for l in launches:
        put, wt = _inside(spans, l)
        assert (put[0], wt[0]) == ("hvd.serve.put",
                                   "hvd.serve.write_through")
        assert l[1] <= put[1] <= put[2] <= wt[1] <= wt[2] <= l[2]
        assert put[3] == {} and wt[3] == {}
    fetches = _named(spans, "fetch")
    assert len(_named(spans, "put")) == len(launches) == \
        len(_named(spans, "write_through")) == len(fetches)
    for mine, following, fetch in zip(launches, launches[1:] + [None],
                                      fetches):
        assert mine[2] <= fetch[1]
        assert following is None or following[2] <= fetch[1]


def test_tracing_moves_no_token_of_any_cache(worked):
    """The same server with nothing on: the same tokens, steps and
    counters."""
    assert tl_mod.get_timeline() is None
    srv, _, tokens = _serve(worked["model"])
    assert tokens == worked["tokens"]
    for name in ("device_steps", "step_no", "occupancy_sum", "tokens_out",
                 "experts_hit_sum", "expert_load_max_sum",
                 "moe_layer_steps", "steps_ahead", "rows_dropped"):
        assert getattr(srv, name) == getattr(worked["srv"], name)


@pytest.mark.parametrize("positions,narrow,want", [
    ([0, 0, 0, 0], 0, 0), ([1, 0, 2, 0], 0, 2 + 3),
    ([3, 4, 9, 0], 0, 4 + 4 + 4), ([3, 4, 9, 0], 2, None),
], ids=["idle", "filling", "wrapped", "two_kinds_of_ring"])
def test_windowed_cache_reckons_the_tokens_in_its_rings(positions, narrow,
                                                        want):
    """`WindowedKVPool.step_args`: `min(pos + 1, window)` over the rows
    that are stepped, beside the pages' share of the view; on the host.
    `ring_tokens` is ONE kind of ring's: a model that also has rings of
    `narrow` slots says the pages' share alone."""
    from horovod_tpu.models.transformer import AttnSpec
    from horovod_tpu.serve.pool import WindowedKVPool
    cfg = _patterned_cfg()
    if narrow:
        cfg = dataclasses.replace(
            cfg, layer_attn=("full", "sliding", "narrow"),
            attn_specs=cfg.attn_specs + (("narrow", AttnSpec(6, narrow)),))
    pool = WindowedKVPool(cfg, 8, 4, rows=4, view_pages=6)
    assert pool.step_args(np.asarray(positions)) == {
        "view_read_pct": 100.0,
        **({} if want is None else {"ring_tokens": want})}


def test_speculative_round_is_one_launch(model, tmp_path):
    cfg, params = model
    (srv, _, _), spans = _profiled(
        tmp_path, lambda: _serve(model, draft_params=params, draft_cfg=cfg,
                                 gamma=2, force_spec=True))
    assert srv.spec_steps > 0
    names = {s[0] for s in spans}
    assert "hvd.serve.launch" in names and "hvd.serve.fetch" not in names
    # a round is no plain step: it says nothing of a step's work, and
    # its launch is not split
    assert all(s[3] == {} for s in spans if s[0] == "hvd.serve.launch")
    assert not names & {"hvd.serve.put", "hvd.serve.write_through"}
    assert all("dstep" not in s[3] for s in spans
               if s[0] == "hvd.serve.observe")


def test_same_tokens_and_steps_with_everything_off(model, traced):
    """Neither a timeline nor a session: the tokens are the plain
    reference's, the step counts the parent's; and tracing moved
    neither."""
    assert tl_mod.get_timeline() is None
    cfg, params = model
    srv, prompts, tokens = _serve(model)
    assert (srv.device_steps, srv.step_no) == \
        (PARENT_DEVICE_STEPS, PARENT_STEPS)
    for rid, prompt in prompts.items():
        want, _ = transformer_generate(params, cfg,
                                       jnp.asarray(prompt[None]),
                                       OUTPUTS[rid])
        assert tokens[rid] == np.asarray(want)[0].tolist()
    assert tokens == traced["tokens"]
    assert (traced["srv"].device_steps, traced["srv"].step_no) == \
        (srv.device_steps, srv.step_no)


# -- the trainer's step --------------------------------------------------

def test_data_parallel_step_is_a_span(tmp_path):
    import optax

    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.zeros((4,))}
    state = opt.init(params)

    def train_step(params, state, batch):
        grads = jax.grad(lambda p: jnp.mean((batch @ p["w"]) ** 2))(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    step = hvd.data_parallel(train_step, batch_args=(2,))
    batch = jnp.ones((16, 4))
    params, state = step(params, state, batch)          # compiles
    tlf = str(tmp_path / "tl.json")
    start_timeline(tlf, mark_cycles=True)
    def three_steps(params=params, state=state):
        for _ in range(3):              # the step donates what it is fed
            params, state = step(params, state, batch)

    try:
        _, spans = _profiled(tmp_path / "prof", three_steps)
    finally:
        stop_timeline()
    assert [s[0] for s in spans] == ["hvd.step.step"] * 3
    steps = [e for e in trace_core.load_events(tlf)
             if e["name"] == "step" and e["cat"] == "step"]
    # written after mark_cycle: each carries the step it measured
    assert [e["step"] for e in steps] == [1, 2, 3]
