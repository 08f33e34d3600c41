"""repro.analysis: static memory model vs measured bytes, kernel audit,
determinism lints, interprocedural dataflow, fingerprints + baseline
workflow, CLI exit-code contract, and the construction-time budget
guards."""

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from proptest import given, settings, st

from repro.analysis import (MemoryBudgetError, load_baseline,
                            memory_report, new_findings, run_checks,
                            validate_params)
from repro.analysis import dataflow, kernel_audit, lints, memory_model
from repro.core.failures import FailSlow
from repro.core.graph import build_workload
from repro.core.recorder import record
from repro.core.routing import Mesh2D
from repro.core.sketch import (STAGE2_SLOT_BYTES, FailSlowSketch,
                               SketchParams)
from repro.core.sloth import Sloth, SlothConfig
from repro.core.streaming import StreamingRecorder

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the clean tree passes; each pass's planted violations are caught
# ---------------------------------------------------------------------------

def test_clean_tree_has_no_unbaselined_findings():
    """Every finding on the committed tree is carried by the committed
    baseline — new fingerprints are regressions."""
    baseline = load_baseline()
    new = new_findings(run_checks("all"), baseline)
    assert new == [], "\n".join(
        f"{f.render()}  fp={f.fingerprint}" for f in new)


def test_memory_self_test():
    memory_model.self_test()


def test_kernel_audit_self_test():
    kernel_audit.self_test()


def test_lints_self_test():
    lints.self_test()


def test_dataflow_self_test():
    dataflow.self_test()


def _cli(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv], cwd=cwd,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True)


def test_cli_exit_codes():
    """--check all --baseline exits 0 on the clean tree; a seeded
    violation (the memory pass under an impossible budget) exits
    nonzero."""
    ok = _cli("--check", "all", "--baseline", "analysis/baseline.json")
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = _cli("--check", "memory", "--budget-kb", "1")
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "over-budget" in bad.stdout


def test_cli_budget_kb_rejected_for_non_memory_checks():
    """--budget-kb used to be silently ignored outside the memory pass;
    now it is a usage error (argparse exit code 2)."""
    r = _cli("--check", "lints", "--budget-kb", "100")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "--budget-kb" in r.stderr
    # still accepted where it applies
    assert _cli("--check", "memory", "--budget-kb", "512")\
        .returncode == 0


def test_cli_json_includes_fingerprints_and_timings():
    r = _cli("--check", "all", "--baseline", "analysis/baseline.json",
             "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["ok"] and doc["new"] == 0
    assert set(doc["timings"]) == {"memory", "kernels", "lints",
                                   "dataflow"}
    assert all(t >= 0 for t in doc["timings"].values())
    for row in doc["findings"]:
        assert row["baselined"] is True
        assert len(row["fingerprint"]) == 16


def test_each_pass_flags_its_synthetic_violation():
    """One seeded violation per pass, through the pass's public unit
    API (the CLI --self-test covers the same ground in CI)."""
    # memory: an over-budget geometry
    rep = memory_report(SketchParams(m=65536), impl="batched")
    assert rep["total_budget_bytes"] > 256 * 1024
    # kernels: a parallel grid writing through an alias
    src = kernel_audit._SYNTHETIC_BAD
    assert any(f.rule == "parallel-write-race"
               for f in kernel_audit.audit_source(src, "<s>"))
    # lints: unseeded global RNG
    fs = lints.lint_source("import numpy as np\n"
                           "x = np.random.rand(3)\n", "<s>")
    assert any(f.rule == "unseeded-rng" for f in fs)


# ---------------------------------------------------------------------------
# memory model == measured bytes (property tests, both impls)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.data())
def test_static_model_matches_array_nbytes(data):
    """Closed forms equal the actual allocated array nbytes across
    randomized geometries, for the ref numpy arrays, the packed jnp
    state, and the drain buffer."""
    from repro.kernels.sketch_update import ref as kref
    p = SketchParams(d=data.draw(st.integers(1, 4)),
                     m=data.draw(st.sampled_from([16, 64, 257, 1024])),
                     H=data.draw(st.integers(1, 8)),
                     L=data.draw(st.sampled_from([8, 33, 256, 1024])))
    cap = data.draw(st.sampled_from([0, 1, 7, 256]))

    sk = FailSlowSketch(p)
    measured_ref = sum(a.nbytes for a in
                       (sk.keys_lo, sk.keys_hi, sk.valid, sk.freq))
    assert measured_ref == memory_model.ref_stage1_nbytes(p)

    state = kref.make_state(p)
    assert sum(int(v.nbytes) for v in state.values()) \
        == memory_model.packed_state_bytes(p)

    drain = kref.make_drain(cap)
    assert sum(int(v.nbytes) for v in drain.values()) \
        == memory_model.drain_bytes(cap)

    assert memory_model.accounting_bytes(p) == p.total_bytes()


@pytest.fixture(scope="module")
def deployment():
    sloth = Sloth(build_workload("darknet19"), Mesh2D(4))
    sim = sloth.run([FailSlow("core", 5, 1.0, 8.0, 10.0)], seed=0)
    return sloth, sim


@pytest.mark.parametrize("impl", ["ref", "batched"])
@pytest.mark.parametrize("geometry", [
    SketchParams(),                         # defaults, no eviction
    SketchParams(d=2, m=256, H=4, L=8),     # forced FIFO eviction
])
def test_static_footprint_equals_measured_onchip(deployment, impl,
                                                 geometry):
    """The execution-free accounting model predicts the measured
    RecorderOutput.onchip_bytes() exactly — including under eviction
    pressure, where the drained stream must be excluded from the
    on-chip figure and accounted at exactly one Stage-2 slot per
    drained pattern."""
    sloth, sim = deployment
    out = record(sim, geometry, hop_latency=sloth.sim_cfg.hop_latency,
                 impl=impl)
    static = 2 * memory_model.accounting_bytes(geometry)  # comp + comm
    assert out.onchip_bytes() == static
    drained = out.n_comp_drained + out.n_comm_drained
    assert out.sketch_bytes == static + drained * STAGE2_SLOT_BYTES
    if geometry.L == 8:
        assert drained > 0   # the small Stage-2 actually evicted


@pytest.mark.parametrize("impl", ["ref", "batched"])
def test_streaming_footprint_matches_static(deployment, impl):
    """The always-on recorder's cumulative output obeys the same static
    accounting (its on-chip state never grows with chunk count)."""
    from repro.core.streaming import split_sim
    sloth, sim = deployment
    p = SketchParams(d=2, m=256, H=4, L=8)
    rec = StreamingRecorder(p, hop_latency=sloth.sim_cfg.hop_latency,
                            impl=impl)
    for chunk in split_sim(sim, 4):
        rec.observe(chunk)
    out = rec.output()
    assert out.onchip_bytes() == 2 * memory_model.accounting_bytes(p)


# ---------------------------------------------------------------------------
# construction-time budget guards
# ---------------------------------------------------------------------------

def test_over_budget_sloth_config_rejected():
    cfg = SlothConfig(sketch=SketchParams(m=65536))
    with pytest.raises(MemoryBudgetError, match="over the .* budget"):
        Sloth(build_workload("darknet19"), Mesh2D(4), cfg=cfg)


def test_budget_none_disables_guard():
    cfg = SlothConfig(sketch=SketchParams(m=4096), budget_kb=None)
    Sloth(build_workload("darknet19"), Mesh2D(4), cfg=cfg)


def test_default_configs_fit_budget():
    Sloth(build_workload("darknet19"), Mesh2D(4))
    Sloth(build_workload("darknet19"), Mesh2D(4),
          cfg=SlothConfig(recorder_impl="batched"))


def test_streaming_recorder_guard():
    with pytest.raises(MemoryBudgetError):
        StreamingRecorder(SketchParams(m=65536))
    StreamingRecorder(SketchParams(m=65536), budget_kb=None)
    with pytest.raises(MemoryBudgetError):
        validate_params(SketchParams(), SketchParams(m=65536))


def test_budget_error_message_is_actionable():
    try:
        StreamingRecorder(SketchParams(m=65536), impl="batched")
    except MemoryBudgetError as e:
        msg = str(e)
        assert "KiB" in msg and "budget_kb" in msg and "m=65536" in msg
    else:
        pytest.fail("no MemoryBudgetError raised")


# ---------------------------------------------------------------------------
# satellite: exact per-slot drained accounting (non-divisible geometry)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _HeaderedParams(SketchParams):
    """A Stage-2 layout with a fixed 24-byte header: stage2_bytes() is
    no longer an exact multiple of L, the case where the historical
    ``stage2_bytes() // L`` per-pattern formula floor-truncates."""

    def stage2_bytes(self) -> int:
        return 24 + self.L * self.stage2_slot_bytes()


def test_drained_accounting_is_exact_per_slot():
    p = _HeaderedParams(d=2, m=1024, H=1, L=7)
    assert p.stage2_bytes() % p.L != 0   # genuinely non-divisible
    sk = FailSlowSketch(p)
    # many distinct keys promoted at H=1 → FIFO evictions past L slots
    for k in range(p.L + 20):
        sk.insert(k + 1, 1.0, 1.0, float(k))
    n = len(sk.drained)
    assert n > 0
    exact = p.total_bytes() + n * STAGE2_SLOT_BYTES
    assert sk.compressed_bytes() == exact
    # the old floor-division formula under-counts on this geometry
    old = p.total_bytes() + n * (p.stage2_bytes() // p.L)
    assert old != exact


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 200), st.integers(2, 31))
def test_slot_accounting_independent_of_L(extra, L):
    """Per-drained-pattern cost is the slot size, never a function of
    L: two sketches with different L but equal drained counts charge
    identical per-pattern bytes."""
    rng = np.random.default_rng(extra * 31 + L)
    p = SketchParams(d=1, m=64, H=1, L=L)
    sk = FailSlowSketch(p)
    for k in range(L + extra):
        sk.insert(int(k + 1), float(rng.random()), 1.0, float(k))
    per = (sk.compressed_bytes() - p.total_bytes()) / max(
        len(sk.drained), 1)
    if sk.drained:
        assert per == STAGE2_SLOT_BYTES


# ---------------------------------------------------------------------------
# kernel audit: the shipped contracts describe the shipped kernels
# ---------------------------------------------------------------------------

def test_kernel_audit_contracts_present_and_consistent():
    findings = kernel_audit.check()
    assert findings == [], "\n".join(f.render() for f in findings)
    files = {f.parent.name: f for f in
             (REPO / "src/repro/kernels").glob("*/kernel.py")}
    assert set(files) == {"sketch_update", "flash_attention",
                          "ssd_scan", "failrank_step"}
    for name, f in files.items():
        assert "AUDIT" in f.read_text(), f"{name} lost its contract"


def test_kernel_audit_catches_grid_rank_drift():
    """Editing a kernel's grid without updating AUDIT is flagged."""
    src = (REPO / "src/repro/kernels/failrank_step/kernel.py")\
        .read_text().replace("grid=(nb,)", "grid=(nb, 2)")
    fs = kernel_audit.audit_source(src, "<mutated>")
    assert any(f.rule == "audit-grid-rank-mismatch" for f in fs)


def test_lint_wallclock_allowlist_is_tight():
    """``spans.clock`` is the package's one blessed wall-clock reader:
    no other file in the lint's scope carries the marker."""
    src = (REPO / "src/repro/core/spans.py").read_text()
    assert src.count("time.perf_counter()") == 1
    assert src.count("# lint: allow-wallclock") == 1
    # stripping the marker re-triggers the lint
    stripped = src.replace("# lint: allow-wallclock", "")
    fs = lints.lint_source(stripped, "<spans>")
    assert any(f.rule == "wallclock" for f in fs)
    marked = sorted(
        str(f.relative_to(REPO)) for scope in lints.WALLCLOCK_SCOPE
        for f in (REPO / "src/repro" / scope).rglob("*.py")
        if "# lint: allow-wallclock" in f.read_text())
    assert marked == ["src/repro/core/spans.py"]


# ---------------------------------------------------------------------------
# dataflow pass: planted violations per rule (interprocedural)
# ---------------------------------------------------------------------------

def _df(modules):
    return dataflow.analyze_modules(modules)


def test_dataflow_literal_seed_flagged():
    fs = _df({"p.core.m": (
        "import jax\n"
        "def k():\n"
        "    return jax.random.PRNGKey(0)\n",
        "src/repro/core/m.py")})
    assert any(f.rule == "literal-seed" for f in fs)


def test_dataflow_seeded_arguments_stay_clean():
    """Scenario-seed lists, cfg fields and CLI --seed all classify as
    seeded; so does a param whose every call site passes a seed."""
    fs = _df({
        "p.core.lib": (
            "import numpy as np\n"
            "def stream(x):\n"
            "    return np.random.default_rng(x)\n",
            "src/repro/core/lib.py"),
        "p.core.use": (
            "from .lib import stream\n"
            "def f(cfg, args, base_seed):\n"
            "    a = stream(cfg.seed)\n"
            "    b = stream(args.seed)\n"
            "    c = stream([base_seed, 3, 7])\n"
            "    return a, b, c\n",
            "src/repro/core/use.py")})
    assert fs == [], "\n".join(f.render() for f in fs)


def test_dataflow_unseeded_provenance_traced_across_modules():
    """An RNG param fed an untraceable value at a call site in another
    module is flagged at the constructor."""
    fs = _df({
        "p.core.lib": (
            "import numpy as np\n"
            "def stream(x):\n"
            "    return np.random.default_rng(x)\n",
            "src/repro/core/lib.py"),
        "p.core.use": (
            "from .lib import stream\n"
            "def f(values):\n"
            "    return stream(len(values))\n",
            "src/repro/core/use.py")})
    hit = [f for f in fs if f.rule == "unseeded-provenance"]
    assert hit and hit[0].path.endswith("lib.py")
    assert hit[0].symbol == "stream"


def test_dataflow_cross_module_narrowing_flagged():
    fs = _df({
        "p.core.pack": (
            "import jax.numpy as jnp\n"
            "def pack(x):\n"
            "    return x.astype(jnp.bfloat16)\n",
            "src/repro/core/pack.py"),
        "p.core.use": (
            "from .pack import pack\n"
            "def f(x):\n"
            "    return pack(x) * 2\n",
            "src/repro/core/use.py")})
    assert any(f.rule == "cross-module-narrowing" for f in fs)
    # same shape with a *widening* cast stays clean
    fs2 = _df({
        "p.core.pack": (
            "import jax.numpy as jnp\n"
            "def pack(x):\n"
            "    return x.astype(jnp.float32)\n",
            "src/repro/core/pack.py"),
        "p.core.use": (
            "from .pack import pack\n"
            "def f(x):\n"
            "    return pack(x) * 2\n",
            "src/repro/core/use.py")})
    assert fs2 == []


def test_dataflow_unsorted_accumulation_flagged():
    src = ("def merge(parts):\n"
           "    acc = 0.0\n"
           "    for v in parts.values():\n"
           "        acc += v\n"
           "    return acc\n")
    fs = _df({"p.core.m": (src, "src/repro/core/m.py")})
    assert any(f.rule == "unsorted-accumulation" for f in fs)
    # integer counters over the same iteration are exact — not flagged
    src_int = ("def count(parts):\n"
               "    n = 0\n"
               "    for v in parts.values():\n"
               "        n += 1\n"
               "    return n\n")
    assert _df({"p.core.m": (src_int, "src/repro/core/m.py")}) == []


def test_dataflow_unordered_sum_and_fixes():
    bad = ("def t(parts):\n"
           "    return sum(parts.values())\n")
    fs = _df({"p.core.m": (bad, "src/repro/core/m.py")})
    assert any(f.rule == "unordered-sum" for f in fs)
    good = ("import math\n"
            "def t(parts):\n"
            "    return sum(sorted(parts.values()))\n"
            "def u(parts):\n"
            "    return math.fsum(parts.values())\n")
    assert _df({"p.core.m": (good, "src/repro/core/m.py")}) == []


# ---------------------------------------------------------------------------
# fingerprints + baseline workflow
# ---------------------------------------------------------------------------

def test_fingerprint_stable_under_line_shifts():
    """Inserting unrelated lines above a finding moves its line but not
    its fingerprint (the baseline keys on symbols, not lines)."""
    body = ("import jax\n"
            "def k():\n"
            "    return jax.random.PRNGKey(0)\n")
    shifted = ("import jax\n"
               "# a comment\n\n\n"
               "def unrelated():\n"
               "    return 1\n\n"
               "def k():\n"
               "    return jax.random.PRNGKey(0)\n")
    f1 = _df({"p.core.m": (body, "src/repro/core/m.py")})
    f2 = _df({"p.core.m": (shifted, "src/repro/core/m.py")})
    assert len(f1) == len(f2) == 1
    assert f1[0].line != f2[0].line
    assert f1[0].symbol == f2[0].symbol == "k"
    assert f1[0].fingerprint == f2[0].fingerprint


def test_fingerprint_distinguishes_rule_and_symbol():
    fs = _df({"p.core.m": (
        "import jax\n"
        "def k1():\n"
        "    return jax.random.PRNGKey(0)\n"
        "def k2():\n"
        "    return jax.random.PRNGKey(7)\n",
        "src/repro/core/m.py")})
    fps = {f.fingerprint for f in fs}
    assert len(fps) == len(fs) == 2


def test_baseline_round_trip(tmp_path):
    """--update-baseline then --baseline exits 0; a planted violation
    in the tree afterwards still exits 1."""
    bl = tmp_path / "bl.json"
    wrote = _cli("--check", "all", "--update-baseline", str(bl))
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    ok = _cli("--check", "all", "--baseline", str(bl))
    assert ok.returncode == 0, ok.stdout + ok.stderr

    planted = REPO / "src/repro/core/_planted_analysis_smoke.py"
    try:
        planted.write_text("import numpy as np\n"
                           "x = np.random.rand(3)\n")
        bad = _cli("--check", "all", "--baseline", str(bl))
        assert bad.returncode == 1, bad.stdout + bad.stderr
        assert "unseeded-rng" in bad.stdout
    finally:
        planted.unlink()


def test_shipped_baseline_is_tight():
    """Every fingerprint in the committed baseline matches a live
    finding — stale entries would mask future regressions."""
    live = {f.fingerprint for f in run_checks("all")}
    baseline = load_baseline()
    stale = set(baseline) - live
    assert not stale, \
        f"stale baseline entries: " \
        f"{ {fp: baseline[fp] for fp in stale} }"
