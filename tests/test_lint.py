"""Tier-1 enforcement + unit tests for the hvdlint static-analysis suite
(scripts/hvdlint/, docs/STATIC_ANALYSIS.md).

The suite itself never imports jax or horovod_tpu; these tests drive it
in-process against synthetic fixture projects (tmp_path trees) and run
`scripts/lint_all.py` against the real repo as the drift gate.
"""

import os
import re
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import hvdlint  # noqa: E402
from hvdlint import (  # noqa: E402
    EnvVarRegistry,
    ExceptionDiscipline,
    JitPurity,
    LockDiscipline,
    Project,
    run_all,
)

MINI_CATALOG = '''\
from dataclasses import dataclass
from typing import Optional

@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str
    component: str
    description: str
    doc: str = ""
    dynamic_site: Optional[str] = None

CATALOG = (
    EnvVar("HOROVOD_KNOWN", "0", "test", "a known knob"),
)
PREFIXES = {"HOROVOD_": "forwarding filter"}

def render_markdown():
    return "# Environment variables\\n"
'''


def make_project(tmp_path, files, catalog=None, env_doc=None):
    """Build a throwaway repo tree: {relpath: source} + optional env
    catalog/doc, and return an hvdlint Project over it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    if catalog is not None:
        p = tmp_path / "horovod_tpu" / "common" / "env_catalog.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(catalog)
    if env_doc is not None:
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        (d / "ENV_VARS.md").write_text(env_doc)
    return Project(tmp_path)


def rules(findings):
    return sorted({(f.analyzer, f.rule) for f in findings})


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

def test_unlocked_write_flagged(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0

            def inc(self):
                with self._lock:
                    self._value += 1

            def set(self, v):
                self._value = v
    """})
    fs = LockDiscipline().run(proj)
    assert [(f.rule, f.line) for f in fs] == [("unlocked-write", 13)]
    assert "Box._value" in fs[0].message


def test_consistently_guarded_class_clean(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0

            def inc(self):
                with self._lock:
                    self._value += 1

            def _drain_locked(self):
                self._value = 0  # caller-holds-the-lock convention
    """})
    assert LockDiscipline().run(proj) == []


def test_unlocked_write_pragma(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0

            def inc(self):
                with self._lock:
                    self._value += 1

            def set(self, v):
                # lint: allow-unlocked(single writer thread by contract)
                self._value = v
    """})
    assert LockDiscipline().run(proj) == []


def test_lock_order_inversion(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def fwd():
            with _a:
                with _b:
                    pass

        def rev():
            with _b:
                with _a:
                    pass
    """})
    fs = LockDiscipline().run(proj)
    assert [f.rule for f in fs] == ["order-inversion"]
    assert "_a" in fs[0].message and "_b" in fs[0].message


def test_lock_order_consistent_clean(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def f():
            with _a:
                with _b:
                    pass

        def g():
            with _a:
                with _b:
                    pass
    """})
    assert LockDiscipline().run(proj) == []


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

def test_impure_traced_decorator(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import time
        import jax

        @jax.jit
        def step(x):
            t0 = time.perf_counter()
            return x + t0
    """})
    fs = JitPurity().run(proj)
    assert [(f.rule, f.line) for f in fs] == [("impure-call", 6)]
    assert "perf_counter" in fs[0].message


def test_impure_fn_passed_to_tracer(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import os
        import jax

        def step(x):
            if os.getenv("HOROVOD_DEBUG"):
                print("tracing", x.shape)
            return x

        fast = jax.jit(step)
    """})
    fs = JitPurity().run(proj)
    assert ("jit-purity", "impure-call") in rules(fs)
    assert {f.line for f in fs} == {5, 6}  # os.getenv + print


def test_partial_jit_and_shard_map_marked(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import logging
        from functools import partial
        import jax
        from jax import shard_map

        logger = logging.getLogger(__name__)

        def inner(x):
            logger.info("traced %s", x)
            return x

        fast = partial(jax.jit, donate_argnums=0)(inner)
        sharded = jax.jit(shard_map(inner, mesh=None))
    """})
    fs = JitPurity().run(proj)
    assert [f.rule for f in fs] == ["impure-call"]
    assert "logging" in fs[0].message


def test_untraced_fn_not_flagged(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import time

        def host_loop(x):
            return time.perf_counter() + x
    """})
    assert JitPurity().run(proj) == []


def test_plain_outer_call_arg_not_traced(tmp_path):
    # jax.jit(f)(x): `x` is a runtime argument, not a traced callable.
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import time
        import jax

        def pure(x):
            return x * 2

        def measure(x):
            return time.monotonic()

        y = jax.jit(pure)(measure(3))
    """})
    assert JitPurity().run(proj) == []


def test_impure_pragma_and_jax_random_ok(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import random
        import jax

        @jax.jit
        def step(key, x):
            n = random.random()  # lint: allow-impure(trace-time seed ok)
            return x + jax.random.uniform(key) + n
    """})
    assert JitPurity().run(proj) == []


def test_nonlocal_mutation_flagged(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import jax

        _count = 0

        @jax.jit
        def step(x):
            global _count
            _count += 1
            return x
    """})
    fs = JitPurity().run(proj)
    assert [f.rule for f in fs] == ["nonlocal-mutation"]


def test_metrics_in_traced_body_flagged(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import jax
        from .metrics import catalog as _met

        @jax.jit
        def step(x):
            _met.collective_calls.labels("allreduce").inc()
            return x
    """})
    fs = JitPurity().run(proj)
    # both the .labels(...) and the .inc() stages of the chain count
    assert {(f.rule, f.line) for f in fs} == {("impure-call", 6)}
    assert any("metrics recording" in f.message for f in fs)


# ---------------------------------------------------------------------------
# env-registry
# ---------------------------------------------------------------------------

def test_unknown_env_literal_and_helper(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import os
        from .common import util

        a = os.environ.get("HOROVOD_MYSTERY")
        b = util.env_bool("ALSO_MYSTERY")
        c = util.env_int("KNOWN", 3)
    """}, catalog=MINI_CATALOG, env_doc="# Environment variables\n")
    fs = EnvVarRegistry().run(proj)
    unknown = sorted((f for f in fs if f.rule == "unknown-env"),
                     key=lambda f: f.line)
    assert [f.line for f in unknown] == [4, 5]
    assert "HOROVOD_MYSTERY" in unknown[0].message
    assert "HOROVOD_ALSO_MYSTERY" in unknown[1].message


def test_dead_entry_and_stale_docs(tmp_path):
    proj = make_project(
        tmp_path, {"horovod_tpu/m.py": "x = 1\n"},
        catalog=MINI_CATALOG, env_doc="out of date\n")
    got = {f.rule for f in EnvVarRegistry().run(proj)}
    assert got == {"dead-entry", "stale-docs"}


def test_dynamic_env_requires_registration(tmp_path):
    src = """\
        from .common import util

        def read(site):
            return util.env_float(f"{site}_RETRY_JITTER", 0.1)
    """
    proj = make_project(tmp_path, {"horovod_tpu/m.py": src},
                        catalog=MINI_CATALOG,
                        env_doc="# Environment variables\n")
    fs = EnvVarRegistry().run(proj)
    assert ("env-registry", "dynamic-env") in rules(fs)

    cat = MINI_CATALOG.replace(
        '"a known knob"),',
        '"a known knob", "", "horovod_tpu/m.py"),')
    src_ok = textwrap.dedent(src) + '\nx = util.getenv("KNOWN")\n'
    proj2 = make_project(tmp_path / "ok", {"horovod_tpu/m.py": src_ok},
                         catalog=cat, env_doc="# Environment variables\n")
    assert [f.rule for f in EnvVarRegistry().run(proj2)] == []


def test_unknown_prefix_literal(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        from .common import util

        FWD = [k for k in ("a",) if k.startswith("HOROVOD_SECRET_")]
        x = util.getenv("KNOWN")
    """}, catalog=MINI_CATALOG, env_doc="# Environment variables\n")
    fs = EnvVarRegistry().run(proj)
    assert [f.rule for f in fs] == ["unknown-prefix"]


def test_missing_catalog(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": "x = 1\n"})
    fs = EnvVarRegistry().run(proj)
    assert [f.rule for f in fs] == ["missing-catalog"]


def test_repo_env_docs_fresh():
    """docs/ENV_VARS.md must byte-match the catalog's renderer."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "gen_env_docs.py"),
         REPO, "--check"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# exception-discipline
# ---------------------------------------------------------------------------

def test_bare_assert_flagged_and_pragma(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        def f(x):
            assert x > 0
            # lint: allow-assert(shape contract checked by caller)
            assert x < 10
            return x
    """})
    fs = ExceptionDiscipline().run(proj)
    assert [(f.rule, f.line) for f in fs] == [("bare-assert", 2)]


def test_silent_swallow_flagged(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        def f():
            try:
                risky()
            except Exception:
                pass
    """})
    fs = ExceptionDiscipline().run(proj)
    assert [(f.rule, f.line) for f in fs] == [("silent-swallow", 4)]


def test_swallow_pragma_and_logged_handler_clean(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        import logging

        def f():
            try:
                risky()
            # lint: allow-swallow(best-effort cleanup at shutdown)
            except Exception:
                pass
            try:
                risky()
            except Exception as e:
                logging.debug("risky failed: %s", e)
            try:
                risky()
            except ValueError:
                pass
    """})
    assert ExceptionDiscipline().run(proj) == []


def test_pragma_without_reason_is_a_finding(tmp_path):
    proj = make_project(tmp_path, {"horovod_tpu/m.py": """\
        def f():
            try:
                risky()
            # lint: allow-swallow()
            except Exception:
                pass
    """})
    fs = run_all(Project(tmp_path), [ExceptionDiscipline()])
    assert rules(fs) == [("exception-discipline", "silent-swallow"),
                        ("pragma", "missing-reason")]


def test_parse_error_reported_once(tmp_path):
    proj = make_project(
        tmp_path, {"horovod_tpu/m.py": "def broken(:\n    pass\n"})
    fs = run_all(proj, [ExceptionDiscipline(), LockDiscipline()])
    assert [(f.analyzer, f.rule) for f in fs] == [("core", "parse-error")]


# ---------------------------------------------------------------------------
# runner / CLI / shims against the real repo
# ---------------------------------------------------------------------------

def test_lint_all_repo_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_all.py"),
         REPO],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analyzer(s) clean" in proc.stdout


def test_lint_all_github_format(tmp_path):
    make_project(tmp_path, {"horovod_tpu/m.py": """\
        def f(x):
            assert x
    """})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_all.py"),
         str(tmp_path), "--format=github",
         "--only=exception-discipline"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert proc.stdout.startswith(
        "::error file=horovod_tpu/m.py,line=2,"
        "title=exception-discipline/bare-assert::")


def test_lint_all_unknown_analyzer():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_all.py"),
         REPO, "--only=nope"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert "unknown analyzer" in proc.stderr


def test_lint_all_list():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_all.py"),
         "--list"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    for a in hvdlint.ALL:
        assert a.name in proc.stdout


def test_no_jax_import_in_lint_machinery():
    """The whole suite must run on a machine without jax."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'scripts'); "
         "sys.modules['jax'] = None; "  # any `import jax` now explodes
         "import lint_all; sys.exit(lint_all.main(['.']))"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# sharded-optimizer catalog coverage (r7 gauges + ag_fusion knob)
# ---------------------------------------------------------------------------

from hvdlint.catalogs import (  # noqa: E402
    MetricsCatalog,
    _DOC_ROW_RE,
    _KNOB_RE,
    _REG_RE,
)

SHARDED_GAUGES = ("hvd_opt_state_bytes", "hvd_rs_bytes",
                  "hvd_param_ag_bytes")


def _repo_text(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def test_sharded_gauges_registered_and_documented():
    """The three ZeRO-1 gauges must exist on BOTH sides the analyzer
    diffs — registered in the catalog and rowed in docs/METRICS.md —
    so deleting either side is a tier-1 failure, not silent drift."""
    declared = set(_REG_RE.findall(
        _repo_text("horovod_tpu/metrics/catalog.py")))
    documented = set(_DOC_ROW_RE.findall(_repo_text("docs/METRICS.md")))
    for gauge in SHARDED_GAUGES:
        assert gauge in declared, gauge
        assert gauge in documented, gauge


def test_ag_fusion_knob_registered_and_documented():
    knobs = set(_KNOB_RE.findall(
        _repo_text("horovod_tpu/utils/autotune.py")))
    assert "ag_fusion" in knobs
    assert "`ag_fusion`" in _repo_text("docs/AUTOTUNE.md")


def test_metrics_catalog_catches_sharded_gauge_doc_drift(tmp_path):
    """Drop one sharded gauge's doc row from a copy of the REAL repo
    files: the metrics-catalog analyzer must flag exactly that gauge."""
    doc = "\n".join(
        line for line in _repo_text("docs/METRICS.md").splitlines()
        if "`hvd_rs_bytes`" not in line)
    proj = make_project(tmp_path, {
        "horovod_tpu/metrics/catalog.py":
            _repo_text("horovod_tpu/metrics/catalog.py"),
        "horovod_tpu/utils/autotune.py":
            _repo_text("horovod_tpu/utils/autotune.py"),
        "docs/METRICS.md": doc,
        "docs/AUTOTUNE.md": _repo_text("docs/AUTOTUNE.md"),
    })
    findings = MetricsCatalog().run(proj)
    assert [(f.rule, "hvd_rs_bytes" in f.message) for f in findings] == [
        ("undocumented-metric", True)]


def test_anomaly_catalog_clean_on_repo():
    """Detector kinds in metrics/anomaly.py and the TELEMETRY.md
    detector table must agree on the real tree."""
    from hvdlint import AnomalyCatalog
    assert AnomalyCatalog().run(Project(REPO)) == []


def test_anomaly_catalog_catches_undocumented_detector(tmp_path):
    """A new detector class with no TELEMETRY.md row must be flagged."""
    from hvdlint import AnomalyCatalog
    src = _repo_text("horovod_tpu/metrics/anomaly.py") + (
        "\n\nclass MadDetector:\n    kind = \"mad_outlier\"\n")
    proj = make_project(tmp_path, {
        "horovod_tpu/metrics/anomaly.py": src,
        "docs/TELEMETRY.md": _repo_text("docs/TELEMETRY.md"),
    })
    findings = AnomalyCatalog().run(proj)
    assert [(f.rule, "mad_outlier" in f.message) for f in findings] == [
        ("undocumented-detector", True)]


def test_anomaly_catalog_catches_stale_doc_row(tmp_path):
    """A detector-catalog row whose class is gone must be flagged."""
    from hvdlint import AnomalyCatalog
    doc = _repo_text("docs/TELEMETRY.md").replace(
        "<!-- detector-catalog:end -->",
        "| `ghost_detector` | nothing | never |\n"
        "<!-- detector-catalog:end -->")
    proj = make_project(tmp_path, {
        "horovod_tpu/metrics/anomaly.py":
            _repo_text("horovod_tpu/metrics/anomaly.py"),
        "docs/TELEMETRY.md": doc,
    })
    findings = AnomalyCatalog().run(proj)
    assert [(f.rule, "ghost_detector" in f.message) for f in findings] \
        == [("stale-doc-entry", True)]


def test_metrics_catalog_catches_ag_fusion_knob_drift(tmp_path):
    """Strip the `ag_fusion` mention from a copy of docs/AUTOTUNE.md:
    the analyzer must report the knob as undocumented."""
    at_doc = _repo_text("docs/AUTOTUNE.md").replace("`ag_fusion`",
                                                    "(redacted)")
    proj = make_project(tmp_path, {
        "horovod_tpu/metrics/catalog.py":
            _repo_text("horovod_tpu/metrics/catalog.py"),
        "horovod_tpu/utils/autotune.py":
            _repo_text("horovod_tpu/utils/autotune.py"),
        "docs/METRICS.md": _repo_text("docs/METRICS.md"),
        "docs/AUTOTUNE.md": at_doc,
    })
    findings = MetricsCatalog().run(proj)
    assert [(f.rule, "ag_fusion" in f.message) for f in findings] == [
        ("undocumented-knob", True)]


# ---------------------------------------------------------------------------
# wire-registry (r6, scripts/hvdlint/wires.py)
# ---------------------------------------------------------------------------

from hvdlint import WireRegistry  # noqa: E402

WIRE_METRICS = ("hvd_wire_bytes_saved", "hvd_wire_bytes_saved_per_step",
                "hvd_wire_format_bytes")


def test_wire_metrics_registered_and_documented():
    declared = set(_REG_RE.findall(
        _repo_text("horovod_tpu/metrics/catalog.py")))
    documented = set(_DOC_ROW_RE.findall(_repo_text("docs/METRICS.md")))
    for metric in WIRE_METRICS:
        assert metric in declared, metric
        assert metric in documented, metric


def test_wire_threshold_knob_registered_and_documented():
    knobs = set(_KNOB_RE.findall(
        _repo_text("horovod_tpu/utils/autotune.py")))
    assert "wire_threshold" in knobs
    assert "`wire_threshold`" in _repo_text("docs/AUTOTUNE.md")


def _wire_project(tmp_path, overrides=None):
    """Copy the real wire module + doc into a fixture tree, with
    optional per-file overrides."""
    files = {
        "horovod_tpu/ops/wire.py": _repo_text("horovod_tpu/ops/wire.py"),
        "docs/WIRE.md": _repo_text("docs/WIRE.md"),
    }
    files.update(overrides or {})
    return make_project(tmp_path, files)


def test_wire_registry_repo_clean():
    assert WireRegistry().run(Project(REPO)) == []


def test_unknown_wire_literal_flagged(tmp_path):
    proj = _wire_project(tmp_path, {
        "horovod_tpu/parallel/bad.py": '''\
            def f(x):
                return reduce(x, wire="int9")
            ''',
    })
    findings = WireRegistry().run(proj)
    assert [(f.rule, "int9" in f.message) for f in findings] == [
        ("unknown-wire", True)]


def test_known_wire_forms_clean(tmp_path):
    proj = _wire_project(tmp_path, {
        "horovod_tpu/parallel/ok.py": '''\
            class C:
                wire = "fp16"

            def f(x, dcn_wire="int4", allgather_wire: str = "bf16"):
                codec = get_codec("fp8_e4m3")
                return reduce(x, wire="int8")
            ''',
    })
    assert WireRegistry().run(proj) == []


def test_wire_doc_drift_both_directions(tmp_path):
    # Drop a codec's doc row -> undocumented-codec.
    doc = "\n".join(
        line for line in _repo_text("docs/WIRE.md").splitlines()
        if not line.startswith("| `int4`"))
    proj = _wire_project(tmp_path, {"docs/WIRE.md": doc})
    findings = WireRegistry().run(proj)
    assert [(f.rule, "int4" in f.message) for f in findings] == [
        ("undocumented-codec", True)]
    # Remove the registration but keep the row -> stale-doc-entry.
    src = _repo_text("horovod_tpu/ops/wire.py").replace(
        'name="int4"', 'name="int8"')
    proj2 = _wire_project(tmp_path, {"horovod_tpu/ops/wire.py": src})
    findings2 = WireRegistry().run(proj2)
    assert ("stale-doc-entry", True) in [
        (f.rule, "int4" in f.message) for f in findings2]


def test_wire_registry_missing_doc_is_error(tmp_path):
    files = {
        "horovod_tpu/ops/wire.py": _repo_text("horovod_tpu/ops/wire.py"),
    }
    proj = make_project(tmp_path, files)
    findings = WireRegistry().run(proj)
    assert [f.rule for f in findings] == ["error"]
    assert "docs/WIRE.md" in findings[0].message


# ---------------------------------------------------------------------------
# training-health guardian (guard/) catalog gates
# ---------------------------------------------------------------------------

from hvdlint import FaultPoints  # noqa: E402
from hvdlint.catalogs import (  # noqa: E402
    _CAT_RE,
    _FAULT_DOC_ROW_RE,
    _SITE_RE,
)

GUARD_METRICS = ("hvd_nonfinite_steps_total", "hvd_loss_scale",
                 "hvd_guard_rollbacks_total", "hvd_digest_mismatch_total")
GUARD_KNOBS = ("loss_scale_growth_interval", "guard_digest_interval")
GUARD_FAULT_POINTS = ("guard.nan_grad", "guard.param_bitflip")
GUARD_ENV_VARS = ("HOROVOD_GUARD", "HOROVOD_GUARD_LOSS_SCALE",
                  "HOROVOD_GUARD_GROWTH_INTERVAL",
                  "HOROVOD_GUARD_DIGEST_INTERVAL",
                  "HOROVOD_GUARD_MAX_NONFINITE",
                  "HOROVOD_CONSISTENCY_TIMEOUT",
                  "HOROVOD_CKPT_QUARANTINE_KEEP")

_ENV_DECL_RE = re.compile(r'_v\(\s*"(HOROVOD_[A-Z0-9_]+)"')
_ENV_DOC_ROW_RE = re.compile(r"^\|\s*`(HOROVOD_[A-Z0-9_]+)`",
                             re.MULTILINE)


def test_guard_metrics_registered_and_documented():
    """The four guardian metrics must exist on BOTH sides the
    metrics-catalog analyzer diffs, so deleting either side is a tier-1
    failure, not silent drift."""
    declared = set(_REG_RE.findall(
        _repo_text("horovod_tpu/metrics/catalog.py")))
    documented = set(_DOC_ROW_RE.findall(_repo_text("docs/METRICS.md")))
    for metric in GUARD_METRICS:
        assert metric in declared, metric
        assert metric in documented, metric


def test_guard_knobs_registered_and_documented():
    knobs = set(_KNOB_RE.findall(
        _repo_text("horovod_tpu/utils/autotune.py")))
    doc = _repo_text("docs/AUTOTUNE.md")
    for knob in GUARD_KNOBS:
        assert knob in knobs, knob
        assert f"`{knob}`" in doc, knob


def test_guard_fault_points_declared_fired_documented():
    declared = set(_CAT_RE.findall(
        _repo_text("horovod_tpu/faults/__init__.py")))
    documented = set(_FAULT_DOC_ROW_RE.findall(
        _repo_text("docs/FAULT_TOLERANCE.md")))
    fired = set(_SITE_RE.findall(
        _repo_text("horovod_tpu/guard/controller.py")))
    for point in GUARD_FAULT_POINTS:
        assert point in declared, point
        assert point in documented, point
        assert point in fired, point


def test_guard_env_vars_cataloged_and_documented():
    declared = set(_ENV_DECL_RE.findall(
        _repo_text("horovod_tpu/common/env_catalog.py")))
    documented = set(_ENV_DOC_ROW_RE.findall(
        _repo_text("docs/ENV_VARS.md")))
    for var in GUARD_ENV_VARS:
        assert var in declared, var
        assert var in documented, var


def test_fault_points_catches_guard_doc_drift(tmp_path):
    """Drop guard.nan_grad's doc row from a copy of the REAL repo
    files: the fault-points analyzer must flag exactly that point."""
    doc = "\n".join(
        line for line in
        _repo_text("docs/FAULT_TOLERANCE.md").splitlines()
        if "`guard.nan_grad`" not in line)
    proj = make_project(tmp_path, {
        "horovod_tpu/faults/__init__.py":
            _repo_text("horovod_tpu/faults/__init__.py"),
        "docs/FAULT_TOLERANCE.md": doc,
    })
    findings = FaultPoints().run(proj)
    # The fixture carries no call sites, so ignore the dead-point noise
    # and check the doc-drift rule precisely.
    assert [(f.rule, "guard.nan_grad" in f.message) for f in findings
            if f.rule == "undocumented-point"] == [
        ("undocumented-point", True)]


# ---------------------------------------------------------------------------
# pallas-guard
# ---------------------------------------------------------------------------

def test_pallas_call_without_interpret_flagged(tmp_path):
    from hvdlint import PallasGuard
    proj = make_project(tmp_path, {"horovod_tpu/k.py": """\
        import jax
        from jax.experimental import pallas as pl  # noqa

        def kern(x):
            return pl.pallas_call(lambda r, o: None,
                                  out_shape=x)(x)
        """})
    got = rules(PallasGuard().run(proj))
    # the bare module-level pallas import is fine: Pallas ships with jax
    assert got == [("pallas-guard", "missing-interpret")]


def test_pallas_static_interpret_flagged(tmp_path):
    from hvdlint import PallasGuard
    proj = make_project(tmp_path, {"horovod_tpu/k.py": """\
        try:
            from jax.experimental import pallas as pl
        except ImportError:
            pl = None

        def kern(x):
            return pl.pallas_call(lambda r, o: None, out_shape=x,
                                  interpret=True)(x)
        """})
    got = rules(PallasGuard().run(proj))
    assert got == [("pallas-guard", "static-interpret")]


def test_pallas_runtime_guard_clean(tmp_path):
    from hvdlint import PallasGuard
    proj = make_project(tmp_path, {"horovod_tpu/k.py": """\
        from jax.experimental import pallas as pl

        def _interpret():
            return False

        def kern(x):
            return pl.pallas_call(lambda r, o: None, out_shape=x,
                                  interpret=_interpret())(x)
        """})
    assert PallasGuard().run(proj) == []


def test_pallas_guard_pragma_suppresses(tmp_path):
    from hvdlint import PallasGuard
    proj = make_project(tmp_path, {"horovod_tpu/k.py": """\
        try:
            from jax.experimental import pallas as pl
        except ImportError:
            pl = None

        def kern(x):
            # lint: allow-static-interpret(debug-only helper)
            return pl.pallas_call(lambda r, o: None, out_shape=x,
                                  interpret=True)(x)
        """})
    assert PallasGuard().run(proj) == []


# ---------------------------------------------------------------------------
# timeline-catalog (fleet tracer, scripts/hvdlint/timeline_cat.py)
# ---------------------------------------------------------------------------

from hvdlint import TimelineCatalog  # noqa: E402

TRACE_INSTANT_ROWS = ("CYCLE_n", "guard_bucket_k", "wire_bucket_k",
                      "fused_bucket_k", "PROFILER_TRACE_START",
                      "serve_submit", "serve_first_token", "serve_evict",
                      "slo_toggle")
SERVE_SPAN_ROWS = ("step", "queue_wait", "prefill", "decode",
                   "admit", "sample", "launch", "fetch", "observe")


def _timeline_doc(rows, span_rows=None):
    table = "\n".join(f"| `{r}` | somewhere | something |" for r in rows)
    doc = ("# Timeline\n\n<!-- instant-catalog:start -->\n"
           "| Instant | Emitted by | Meaning |\n|---|---|---|\n"
           f"{table}\n<!-- instant-catalog:end -->\n")
    if span_rows is not None:
        spans = "\n".join(f"| `{r}` | somewhere | something |"
                          for r in span_rows)
        doc += ("\n<!-- span-catalog:start -->\n"
                "| Span | Emitted by | Meaning |\n|---|---|---|\n"
                f"{spans}\n<!-- span-catalog:end -->\n")
    return doc


def test_timeline_catalog_clean_fixture(tmp_path):
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            MARKER = "PROFILER_TRACE_START"

            def f(tl, k):
                tl.instant(f"wire_bucket_{k}", category="wire")
                tl.instant(MARKER, category="profiler")
            ''',
        "docs/TIMELINE.md": _timeline_doc(
            ("wire_bucket_k", "PROFILER_TRACE_START")),
    })
    assert TimelineCatalog().run(proj) == []


def test_timeline_catalog_undocumented_instant(tmp_path):
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            def f(tl, n):
                tl.instant(f"CYCLE_{n}", category="cycle")
                tl.instant("surprise_marker", category="event")
            ''',
        "docs/TIMELINE.md": _timeline_doc(("CYCLE_n",)),
    })
    findings = TimelineCatalog().run(proj)
    assert [(f.rule, "surprise_marker" in f.message) for f in findings] \
        == [("undocumented-instant", True)]
    assert findings[0].path == "horovod_tpu/a.py"


def test_timeline_catalog_stale_doc_entry(tmp_path):
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            def f(tl, n):
                tl.instant(f"CYCLE_{n}", category="cycle")
            ''',
        "docs/TIMELINE.md": _timeline_doc(("CYCLE_n", "ghost_marker")),
    })
    findings = TimelineCatalog().run(proj)
    assert [(f.rule, "ghost_marker" in f.message) for f in findings] \
        == [("stale-doc-entry", True)]
    assert findings[0].path == "docs/TIMELINE.md"


def test_timeline_catalog_missing_section_is_error(tmp_path):
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            def f(tl):
                tl.instant("evt")
            ''',
        "docs/TIMELINE.md": "# Timeline\n\nno catalog table here\n",
    })
    findings = TimelineCatalog().run(proj)
    assert [f.rule for f in findings] == ["error"]
    assert "instant-catalog" in findings[0].message


def test_timeline_catalog_span_drift_both_directions(tmp_path):
    """The span catalog is linted like the instant catalog: an emitted
    `.complete()` name with no row, and a rowed span emitted nowhere,
    are both findings."""
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            def f(tl, t0):
                tl.complete("queue_wait", category="serve", start_us=t0)
                tl.complete("mystery_span", category="serve", start_us=t0)
                tl.instant("evt", category="event")
            ''',
        "docs/TIMELINE.md": _timeline_doc(
            ("evt",), span_rows=("queue_wait", "ghost_span")),
    })
    findings = TimelineCatalog().run(proj)
    assert sorted((f.rule, f.path) for f in findings) == [
        ("stale-doc-entry", "docs/TIMELINE.md"),
        ("undocumented-span", "horovod_tpu/a.py"),
    ]
    assert any("mystery_span" in f.message for f in findings)
    assert any("ghost_span" in f.message for f in findings)


def test_timeline_catalog_sees_span_primitive_call_sites(tmp_path):
    """`span("name", "category")` (utils/timeline.span) writes the same
    complete event as `tl.complete("name", ...)`: its call sites are
    linted against the span catalog in both directions too."""
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            from .utils.timeline import span
            from .utils import timeline as _tl

            def f(tl, t0):
                with span("fetch", "serve"):
                    pass
                with _tl.span("mystery_phase", "serve", {"n": 1}):
                    pass
                tl.instant("evt", category="event")
                wingspan("not_a_span")
            ''',
        "docs/TIMELINE.md": _timeline_doc(
            ("evt",), span_rows=("fetch", "ghost_span")),
    })
    findings = TimelineCatalog().run(proj)
    assert sorted((f.rule, f.path) for f in findings) == [
        ("stale-doc-entry", "docs/TIMELINE.md"),
        ("undocumented-span", "horovod_tpu/a.py"),
    ]
    assert any("mystery_phase" in f.message for f in findings)
    assert not any("not_a_span" in f.message for f in findings)


def test_timeline_catalog_spans_need_section_only_when_emitted(tmp_path):
    """No `.complete()` call sites -> no span table required (the
    instant-only fixtures above); emitted spans without a span-catalog
    section -> error."""
    proj = make_project(tmp_path, {
        "horovod_tpu/a.py": '''\
            def f(tl, t0):
                tl.complete("queue_wait", category="serve", start_us=t0)
                tl.instant("evt", category="event")
            ''',
        "docs/TIMELINE.md": _timeline_doc(("evt",)),
    })
    findings = TimelineCatalog().run(proj)
    assert [f.rule for f in findings] == ["error"]
    assert "span-catalog" in findings[0].message


def test_trace_instants_emitted_and_documented():
    """Every fleet-tracer instant family must exist on BOTH sides the
    timeline-catalog analyzer diffs — emitted in the package and rowed
    in docs/TIMELINE.md — so deleting either side is a tier-1 failure."""
    from hvdlint.timeline_cat import _SPAN_SECTION_RE, _doc_rows
    doc = _repo_text("docs/TIMELINE.md")
    rows = set(_doc_rows(doc))
    for name in TRACE_INSTANT_ROWS:
        assert name in rows, name
    spans = set(_doc_rows(doc, _SPAN_SECTION_RE))
    for name in SERVE_SPAN_ROWS:
        assert name in spans, name
    assert TimelineCatalog().run(Project(REPO)) == []


def test_trace_gauges_registered_and_documented():
    """The tracer's continuous surface (docs/TRACE.md) in the metrics
    catalog and docs/METRICS.md, both directions."""
    declared = set(_REG_RE.findall(
        _repo_text("horovod_tpu/metrics/catalog.py")))
    documented = set(_DOC_ROW_RE.findall(_repo_text("docs/METRICS.md")))
    for gauge in ("hvd_critical_path_ms", "hvd_step_skew_ms",
                  "hvd_straggler_rank", "hvd_stall_laggards"):
        assert gauge in declared, gauge
        assert gauge in documented, gauge


def test_trace_env_vars_cataloged_and_documented():
    declared = set(_ENV_DECL_RE.findall(
        _repo_text("horovod_tpu/common/env_catalog.py")))
    documented = set(_ENV_DOC_ROW_RE.findall(
        _repo_text("docs/ENV_VARS.md")))
    for var in ("HOROVOD_TRACE_STEP_SPANS", "HOROVOD_TRACE_ALIGN",
                "HOROVOD_TRACE_FLOW_EVENTS"):
        assert var in declared, var
        assert var in documented, var
