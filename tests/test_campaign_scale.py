"""Campaign scale-up axes: process-pool execution, multi-failure
scenarios (``n_failures``), rectangular meshes, overlap semantics and the
weighted probe-overhead aggregation."""

import dataclasses

import pytest

from repro.core.campaign import (CampaignGrid, DeploymentCache,
                                 enumerate_scenarios, materialise,
                                 run_campaign)
from repro.core.failures import FailSlow
from repro.core.metrics import (DetectorOutcome, ScenarioOutcome, aggregate,
                                recall_stat, topk_stat)
from repro.core.routing import Mesh2D
from repro.core.simulator import simulate

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

TINY = CampaignGrid(workloads=("darknet19",), meshes=(4,),
                    kinds=("core", "link", "router", "none"),
                    severities=(8.0,), n_failures=(1, 2), reps=1,
                    campaign_seed=21)


@pytest.fixture(scope="module")
def serial_result():
    return run_campaign(TINY, workers=0, cache=DeploymentCache())


# ---------------------------------------------------------------------------
# process-pool executor
# ---------------------------------------------------------------------------

def test_process_pool_bit_identical(serial_result):
    """`executor='process'` (per-worker deployment caches, spawn start
    method) reproduces serial execution outcome-for-outcome."""
    res = run_campaign(TINY, workers=2, executor="process")
    assert res.outcomes == serial_result.outcomes
    assert res.metrics == serial_result.metrics
    assert res.cells == serial_result.cells
    assert res.probe_overheads == serial_result.probe_overheads


def test_process_executor_serial_fallback(serial_result):
    """workers<=1 under the process executor runs in-process (no pool)."""
    res = run_campaign(TINY, workers=1, executor="process",
                       cache=DeploymentCache())
    assert res.outcomes == serial_result.outcomes


def test_process_executor_refused_on_tpu(monkeypatch, serial_result):
    """A TPU belongs to one process: on a TPU backend the process pool
    is refused before any worker spawns, pointing at the executors that
    stay in-process; serial runs under the same backend still go."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="executor='thread'"):
        run_campaign(TINY, workers=2, executor="process")
    res = run_campaign(TINY, workers=1, executor="process",
                       cache=DeploymentCache())
    assert res.outcomes == serial_result.outcomes


def test_unknown_executor_rejected():
    with pytest.raises(ValueError, match="unknown executor"):
        run_campaign(TINY, executor="gremlin")


# ---------------------------------------------------------------------------
# n_failures axis
# ---------------------------------------------------------------------------

def test_n_failures_axis_enumeration():
    scen = enumerate_scenarios(TINY)
    assert len(scen) == TINY.n_scenarios()
    # 3 positive kinds × 1 severity × 2 n_failures + 1 collapsed 'none'
    assert len(scen) == 3 * 2 + 1
    for s in scen:
        if s.kind == "none":
            assert s.n_failures == 0
        else:
            assert s.n_failures in TINY.n_failures


def test_grid_rejects_bad_n_failures():
    with pytest.raises(ValueError, match="n_failures"):
        CampaignGrid(n_failures=(0,))
    with pytest.raises(ValueError, match="n_failures"):
        CampaignGrid(n_failures=())


def test_multi_failure_materialise_distinct_locations():
    cache = DeploymentCache()
    dep = cache.get("darknet19", 4, 4)
    for s in enumerate_scenarios(TINY):
        failures, _ = materialise(TINY, s, dep)
        assert len(failures) == s.n_failures
        locs = [f.location for f in failures]
        assert len(set(locs)) == len(locs)       # distinct placements
        assert all(f.kind == s.kind for f in failures)
        assert all(f.slowdown == s.severity for f in failures)


def test_materialise_rejects_oversized_k():
    cache = DeploymentCache()
    dep = cache.get("darknet19", 4, 4)
    big = dataclasses.replace(TINY, n_failures=(10_000,))
    s = next(s for s in enumerate_scenarios(big) if s.kind == "core")
    with pytest.raises(ValueError, match="cannot place"):
        materialise(big, s, dep)
    s = next(s for s in enumerate_scenarios(big) if s.kind == "link")
    with pytest.raises(ValueError, match="cannot place"):
        materialise(big, s, dep)


def test_multi_failure_outcomes_judged_per_failure(serial_result):
    """k=2 scenarios carry two truths, each with its own rank; the
    scenario-level truth_rank is the best of them."""
    multi = [o for o in serial_result.outcomes if o.n_failures == 2]
    assert multi
    for o in multi:
        assert len(o.truth_locations) == 2
        assert len(o.truth_ranks) == 2
        ranked = [r for r in o.truth_ranks if r is not None]
        assert o.truth_rank == (min(ranked) if ranked else None)
        if o.matched:
            assert o.flagged


def test_recall_at_k_in_summary(serial_result):
    s = serial_result.summary()
    assert "recall@1" in s and "recall@3" in s and "recall@5" in s


# ---------------------------------------------------------------------------
# judging semantics on synthetic outcomes (pure metric unit tests)
# ---------------------------------------------------------------------------

def _outcome(i, kind="core", truth_ranks=(), matched=False, flagged=True,
             workload="wl", mesh=(4, 4), probe_overhead=0.0,
             detector="sloth"):
    n = len(truth_ranks)
    ranked = [r for r in truth_ranks if r is not None]
    det = DetectorOutcome(
        detector=detector, flagged=flagged, pred_kind="core",
        pred_location=0, score=1.0, matched=matched,
        truth_rank=min(ranked) if ranked else None,
        truth_ranks=tuple(truth_ranks))
    return ScenarioOutcome(
        scenario_id=i, workload=workload, mesh_w=mesh[0], mesh_h=mesh[1],
        kind=kind, severity=8.0 if kind != "none" else 0.0,
        n_failures=n, rep=0, sim_seed=i,
        truth_locations=tuple(range(n)), truth_t0s=(0.0,) * n,
        truth_durations=(1.0,) * n, detector_results=(det,),
        compression_ratio=10.0,
        total_time=1.0, probe_overhead=probe_overhead)


def test_recall_counts_individual_failures():
    outs = [
        _outcome(0, truth_ranks=(1, 4), matched=True),    # 2 failures
        _outcome(1, truth_ranks=(2, None), matched=False),
        _outcome(2, kind="none", flagged=False),          # no recall trials
    ]
    r1 = recall_stat(outs, 1)
    assert (r1.successes, r1.trials) == (1, 4)
    r3 = recall_stat(outs, 3)
    assert (r3.successes, r3.trials) == (2, 4)
    r5 = recall_stat(outs, 5)
    assert (r5.successes, r5.trials) == (3, 4)
    # scenario-level top-k uses the best rank per scenario
    t1 = topk_stat(outs, 1)
    assert (t1.successes, t1.trials) == (1, 2)
    t2 = topk_stat(outs, 2)
    assert (t2.successes, t2.trials) == (2, 2)
    m = aggregate(outs)
    assert m.recall_at(1) == 0.25 and m.accuracy.rate == 0.5


def test_probe_overhead_weighted_by_scenario_count():
    """Deployment A serves 3 scenarios, deployment B serves 1: the
    headline mean weights by scenario count; the unweighted mean does
    not."""
    outs = ([_outcome(i, workload="a", probe_overhead=0.01)
             for i in range(3)]
            + [_outcome(3, workload="b", probe_overhead=0.09)])
    m = aggregate(outs)
    assert m.mean_probe_overhead == pytest.approx((3 * 0.01 + 0.09) / 4)
    assert m.mean_probe_overhead_unweighted == pytest.approx(0.05)


# ---------------------------------------------------------------------------
# rectangular meshes
# ---------------------------------------------------------------------------

def test_mesh_spec_normalisation():
    g = CampaignGrid(meshes=(4, (6, 3), "12x8"))
    assert g.meshes == ((4, 4), (6, 3), (12, 8))
    with pytest.raises(ValueError, match="mesh"):
        CampaignGrid(meshes=("4x4x4",))
    with pytest.raises(ValueError, match="mesh"):
        CampaignGrid(meshes=((4, 4, 4),))
    with pytest.raises(ValueError, match="mesh"):
        CampaignGrid(meshes=(0,))


def test_rect_mesh_routing_link_id_round_trip():
    mesh = Mesh2D(6, 3)
    assert mesh.n_cores == 18
    # link ids and endpoint pairs are mutually inverse
    for lid, (u, v) in enumerate(mesh.links):
        assert mesh.link_id(u, v) == lid
    # XY routes walk adjacent links from src to dst with hop-count length
    for src, dst in ((0, 17), (5, 12), (13, 2), (7, 7)):
        path = mesh.route(src, dst)
        assert len(path) == mesh.hops(src, dst)
        cur = src
        for lid in path:
            u, v = mesh.links[lid]
            assert u == cur
            cur = v
        assert cur == dst


def test_rect_mesh_campaign_end_to_end():
    g = CampaignGrid(workloads=("darknet19",), meshes=("6x3",),
                     kinds=("core", "none"), severities=(8.0,),
                     reps=1, campaign_seed=5)
    res = run_campaign(g, workers=0, cache=DeploymentCache())
    assert all(o.mesh_w == 6 and o.mesh_h == 3 for o in res.outcomes)
    assert ("darknet19", "mesh", 6, 3) in res.probe_overheads
    assert all(c[1] == 6 and c[2] == 3 for c in res.cells)


def test_12x12_multi_failure_campaign():
    """Acceptance: a 12×12-mesh, n_failures=2 campaign runs end-to-end
    with per-failure recall reported."""
    g = CampaignGrid(workloads=("darknet19",), meshes=("12x12",),
                     kinds=("core", "link"), severities=(10.0,),
                     n_failures=(2,), reps=1, campaign_seed=2)
    res = run_campaign(g, workers=0, cache=DeploymentCache())
    assert len(res.outcomes) == 2
    assert all(o.mesh_w == o.mesh_h == 12 for o in res.outcomes)
    assert all(o.n_failures == 2 for o in res.outcomes)
    rec = dict(res.metrics.recall)
    assert rec[5].trials == 4            # 2 scenarios × 2 failures
    assert "recall@5" in res.summary()


# ---------------------------------------------------------------------------
# simulator multi-failure overlap semantics
# ---------------------------------------------------------------------------

def test_overlapping_failures_compound():
    """Two overlapping windows on one resource compound multiplicatively
    instead of silently overwriting each other."""
    cache = DeploymentCache()
    dep = cache.get("darknet19", 4, 4)
    sloth = dep.sloth
    cfg = dataclasses.replace(sloth.sim_cfg, seed=0)
    core = 5
    horizon = dep.healthy.total_time * 4
    one = FailSlow("core", core, 0.0, horizon, 4.0)
    two = FailSlow("core", core, 0.0, horizon, 4.0)
    t_base = simulate(sloth.mapped, cfg).total_time
    t_one = simulate(sloth.mapped, cfg, failures=[one]).total_time
    t_two = simulate(sloth.mapped, cfg, failures=[one, two]).total_time
    assert t_base < t_one < t_two


def test_two_routers_slowing_shared_link_compound():
    mesh = Mesh2D(4)
    cache = DeploymentCache()
    dep = cache.get("darknet19", 4, 4)
    sloth = dep.sloth
    cfg = dataclasses.replace(sloth.sim_cfg, seed=0)
    # adjacent routers share the link between them
    shared = set(mesh.links_of_router(5)) & set(mesh.links_of_router(6))
    assert shared
    horizon = dep.healthy.total_time * 4
    r5 = FailSlow("router", 5, 0.0, horizon, 3.0)
    r6 = FailSlow("router", 6, 0.0, horizon, 3.0)
    t_one = simulate(sloth.mapped, cfg, failures=[r5]).total_time
    t_two = simulate(sloth.mapped, cfg, failures=[r5, r6]).total_time
    assert t_two > t_one
