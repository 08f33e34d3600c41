"""Static (execution-free) model of the recorder's on-chip footprint.

SLOTH's headline claim is that on-chip detection is practical because the
Fail-Slow Sketch fits in kilobytes of SRAM.  This module makes that claim
*checkable at construction time*: closed-form byte counts for every
resident structure, derived only from :class:`~repro.core.sketch.
SketchParams` / ``SlothConfig`` — no arrays are allocated, no JAX is
imported.

Three layers of accounting, each matching a measured quantity exactly
(property-tested in ``tests/test_analysis.py``):

* **Paper accounting** (``accounting_bytes`` = ``SketchParams.
  total_bytes()``): Stage-1 ``d×m`` (lo, hi, freq) entries at 12 B plus
  Stage-2 ``L`` slots at :data:`~repro.core.sketch.STAGE2_SLOT_BYTES`
  each — the figure every compression ratio in the campaign uses, and
  what ``RecorderOutput.onchip_bytes()`` reports.
* **Ref-impl arrays** (``ref_stage1_nbytes``): the numpy oracle's actual
  Stage-1 array ``nbytes`` (int32 lo + int32 hi + bool valid + int64
  freq = 17 B/bucket).
* **Batched-impl arrays** (``packed_state_bytes`` / ``drain_bytes`` /
  ``pallas_vmem_bytes``): the packed jnp state of
  ``kernels/sketch_update/ref.make_state`` (4 int32 ``[d,m]`` tables +
  11 ``[L]`` vectors (5 int32, 6 f32) + a scalar counter), the
  drained-eviction buffer of ``make_drain`` (10 ``[cap]`` arrays + a
  scalar), and the Pallas kernel's full on-chip set in SMEM and VMEM
  (those two plus the 6 streamed trace arrays of one ``block``) —
  mirroring the ``BlockSpec`` shapes in
  ``kernels/sketch_update/kernel.py``.

The budget check (:func:`validate_config` / :func:`validate_params`)
gates the *persistent* per-chip footprint: for each side (comp + comm)
the larger of the accounting bytes and — on ``impl="batched"`` — the
packed-state bytes, summed, against ``budget_kb`` KiB.  It is wired into
``Sloth.__init__`` and ``StreamingRecorder.__init__`` so an over-budget
geometry is rejected before any trace is recorded.
"""

from __future__ import annotations

from ..core.sketch import STAGE2_SLOT_BYTES, SketchParams
from .report import Finding

#: Default per-chip budget, KiB (1 KiB = 1024 B).  The paper's recorder
#: operates in the hundreds-of-KiB SRAM regime (Figs 11/12 report
#: per-side sketch storage well under this); the repo's default geometry
#: (d=2, m=1024, L=1024, both sides) uses 128 KiB accounting / ~152 KiB
#: packed — comfortably inside, while leaving headroom for the pod
#: telemetry geometry (L=2048, ~240 KiB packed).
DEFAULT_BUDGET_KB = 256.0

#: Bytes per Stage-1 bucket in the paper accounting (lo + hi + freq).
STAGE1_ENTRY_BYTES = 4 + 4 + 4

#: Bytes per Stage-1 bucket in the numpy oracle's actual arrays
#: (int32 lo + int32 hi + bool valid + int64 freq).
REF_STAGE1_ENTRY_BYTES = 4 + 4 + 1 + 8

#: Bytes per Stage-1 bucket in the packed jnp state (4 int32 tables).
PACKED_STAGE1_ENTRY_BYTES = 4 * 4

#: Bytes per Stage-2 slot in the packed jnp state (5 int32 + 6 f32
#: vectors: lo, hi, valid, count, arrival / sum, sumsq, val, tmin, tmax,
#: min).
PACKED_STAGE2_SLOT_BYTES = 5 * 4 + 6 * 4

#: Bytes per drained-eviction row (10 × 4 B arrays in ``make_drain``).
DRAIN_ROW_BYTES = 10 * 4

#: Streamed trace arrays in the Pallas kernel: lo, hi, act (int32) +
#: dur, val, t (f32) — bytes per record of one grid block.
TRACE_RECORD_BYTES = 6 * 4


class MemoryBudgetError(ValueError):
    """A sketch geometry exceeds the configured on-chip byte budget."""


# -- closed forms ------------------------------------------------------------

def accounting_bytes(p: SketchParams) -> int:
    """Paper accounting for one side: Stage-1 + Stage-2
    (= ``p.total_bytes()``, restated here as the model's ground truth)."""
    return (p.d * p.m * STAGE1_ENTRY_BYTES
            + p.L * STAGE2_SLOT_BYTES)


def ref_stage1_nbytes(p: SketchParams) -> int:
    """Summed ``nbytes`` of the numpy oracle's Stage-1 arrays
    (``FailSlowSketch.keys_lo/keys_hi/valid/freq``)."""
    return p.d * p.m * REF_STAGE1_ENTRY_BYTES


def packed_state_bytes(p: SketchParams) -> int:
    """Summed ``nbytes`` of ``kernels/sketch_update/ref.make_state(p)``:
    4 Stage-1 tables, 11 Stage-2 vectors, the scalar arrival counter."""
    return (p.d * p.m * PACKED_STAGE1_ENTRY_BYTES
            + p.L * PACKED_STAGE2_SLOT_BYTES
            + 4)


def drain_bytes(capacity: int) -> int:
    """Summed ``nbytes`` of ``kernels/sketch_update/ref.make_drain``:
    10 per-row arrays (capacity floored at 1) plus the scalar ``d_n``."""
    return max(int(capacity), 1) * DRAIN_ROW_BYTES + 4


def pallas_vmem_bytes(p: SketchParams, *, block: int = 256,
                      drain_capacity: int = 256) -> int:
    """On-chip set of one ``sketch_insert`` call, SMEM and VMEM together:
    the streamed trace block (``trace_spec`` × 6 arrays), the aliased
    packed state (pinned across the sequential grid), and the drain
    buffer.  Derived
    from the BlockSpec shapes in ``kernels/sketch_update/kernel.py``."""
    return (block * TRACE_RECORD_BYTES
            + packed_state_bytes(p)
            + drain_bytes(drain_capacity))


def side_budget_bytes(p: SketchParams, impl: str) -> int:
    """Persistent on-chip bytes one side of the recorder must hold:
    the paper accounting, or the packed jnp state when that is larger
    (``impl="batched"`` keeps the packed layout resident)."""
    b = accounting_bytes(p)
    if impl == "batched":
        b = max(b, packed_state_bytes(p))
    return b


# -- reporting ---------------------------------------------------------------

def memory_report(params: SketchParams,
                  comm_params: SketchParams | None = None,
                  impl: str = "ref", *, block: int = 256) -> dict:
    """Full per-chip footprint breakdown for one recorder geometry.
    Pure arithmetic — safe to call from the CLI and from tests without
    touching JAX."""
    comm_params = comm_params or params
    sides = {"comp": params, "comm": comm_params}
    rep: dict = {"impl": impl, "sides": {}}
    for name, p in sides.items():
        rep["sides"][name] = {
            "params": {"d": p.d, "m": p.m, "H": p.H, "L": p.L},
            "accounting_bytes": accounting_bytes(p),
            "stage1_bytes": p.stage1_bytes(),
            "stage2_bytes": p.stage2_bytes(),
            "ref_stage1_nbytes": ref_stage1_nbytes(p),
            "packed_state_bytes": packed_state_bytes(p),
            "pallas_vmem_bytes": pallas_vmem_bytes(
                p, block=block, drain_capacity=block),
            "budget_bytes": side_budget_bytes(p, impl),
        }
    rep["total_budget_bytes"] = sum(
        s["budget_bytes"] for s in rep["sides"].values())
    return rep


def _over_budget_message(rep: dict, budget_kb: float) -> str | None:
    total = rep["total_budget_bytes"]
    if total <= budget_kb * 1024:
        return None
    parts = ", ".join(
        f"{name}: d={s['params']['d']} m={s['params']['m']} "
        f"L={s['params']['L']} → {s['budget_bytes']} B"
        for name, s in rep["sides"].items())
    return (f"sketch geometry needs {total} B "
            f"({total / 1024:.1f} KiB) on-chip for impl="
            f"{rep['impl']!r}, over the {budget_kb:g} KiB budget "
            f"({parts}); shrink d/m/L or raise budget_kb")


# -- construction-time guards ------------------------------------------------

def validate_params(params: SketchParams,
                    comm_params: SketchParams | None = None,
                    impl: str = "ref",
                    budget_kb: float | None = DEFAULT_BUDGET_KB) -> None:
    """Raise :class:`MemoryBudgetError` if the comp+comm sketch geometry
    cannot fit the per-chip ``budget_kb`` KiB budget under ``impl``.
    ``budget_kb=None`` disables the check (benchmark sweeps deliberately
    explore over-budget geometries through the unguarded ``record``)."""
    if budget_kb is None:
        return
    rep = memory_report(params, comm_params, impl)
    msg = _over_budget_message(rep, budget_kb)
    if msg is not None:
        raise MemoryBudgetError(msg)


def validate_config(cfg) -> None:
    """Construction guard for ``SlothConfig``-shaped configs: check
    ``cfg.sketch`` (both sides) against ``cfg.budget_kb`` under
    ``cfg.recorder_impl``.  Duck-typed so ``PodTelemetryConfig`` (same
    three fields) validates through the same door.  Raises
    :class:`MemoryBudgetError`; a config with ``budget_kb=None`` is
    exempt."""
    validate_params(cfg.sketch,
                    impl=getattr(cfg, "recorder_impl", "ref"),
                    budget_kb=getattr(cfg, "budget_kb",
                                      DEFAULT_BUDGET_KB))


# -- CLI pass ----------------------------------------------------------------

def check(root=None, budget_kb: float | None = None) -> list[Finding]:
    """Static memory pass over the repo's shipped geometries: the default
    ``SlothConfig`` and the pod-telemetry config must fit their budgets,
    and the closed forms above must agree with the authoritative
    ``SketchParams`` byte methods (drift in either is a finding).
    ``root`` is accepted for pass-signature uniformity and unused."""
    findings: list[Finding] = []

    def against(label: str, path: str, params, impl, kb) -> None:
        rep = memory_report(params, impl=impl)
        msg = _over_budget_message(rep, kb)
        if msg is not None:
            findings.append(Finding("memory", "over-budget", path, 0,
                                    f"{label}: {msg}"))

    # model drift: the closed forms must restate SketchParams exactly
    for p in (SketchParams(), SketchParams(d=3, m=7, H=2, L=5)):
        if accounting_bytes(p) != p.total_bytes():
            findings.append(Finding(
                "memory", "model-drift", "src/repro/core/sketch.py", 0,
                f"accounting_bytes({p}) = {accounting_bytes(p)} != "
                f"SketchParams.total_bytes() = {p.total_bytes()}"))
        if p.stage2_bytes() != p.L * STAGE2_SLOT_BYTES:
            findings.append(Finding(
                "memory", "model-drift", "src/repro/core/sketch.py", 0,
                f"stage2_bytes({p}) is not L * STAGE2_SLOT_BYTES "
                f"({p.stage2_bytes()} != {p.L * STAGE2_SLOT_BYTES})"))

    from ..core.sloth import SlothConfig
    cfg = SlothConfig()
    kb = budget_kb if budget_kb is not None else cfg.budget_kb
    for impl in ("ref", "batched"):
        against(f"default SlothConfig (impl={impl})",
                "src/repro/core/sloth.py", cfg.sketch, impl, kb)

    try:
        from ..distributed.telemetry import PodTelemetryConfig
    except Exception:   # distributed extras may be absent in slim builds
        pass
    else:
        pod = PodTelemetryConfig()
        pod_kb = budget_kb if budget_kb is not None \
            else getattr(pod, "budget_kb", DEFAULT_BUDGET_KB)
        against("PodTelemetryConfig",
                "src/repro/distributed/telemetry.py", pod.sketch,
                getattr(pod, "recorder_impl", "ref"), pod_kb)
    return findings


def self_test() -> None:
    """Plant a synthetic violation and assert the pass catches it."""
    # clean tree: shipped geometries fit
    assert check() == [], f"clean-tree memory findings: {check()}"
    # synthetic violation: a 64k-bucket Stage-1 blows the default budget
    big = SketchParams(m=65536)
    rep = memory_report(big, impl="batched")
    msg = _over_budget_message(rep, DEFAULT_BUDGET_KB)
    assert msg is not None, "over-budget geometry not flagged"
    try:
        validate_params(big, budget_kb=DEFAULT_BUDGET_KB)
    except MemoryBudgetError:
        pass
    else:
        raise AssertionError("validate_params accepted an over-budget "
                             "geometry")
    # the guard honours budget_kb=None (benchmarks explore big sweeps)
    validate_params(big, budget_kb=None)
    # seeding the CLI pass with a tiny budget must produce findings
    planted = check(budget_kb=1.0)
    assert any(f.rule == "over-budget" for f in planted), \
        "check(budget_kb=1.0) produced no over-budget finding"
