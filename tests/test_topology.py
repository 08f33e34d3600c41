"""Topology abstraction layer: the fabric registry, the per-topology
routing contract, campaign threading across torus / systolic /
heterogeneous fabrics, and the bit-identity regression pinning default
mesh campaigns to the pre-refactor snapshot."""

import json
import pathlib

import numpy as np
import pytest

from repro.core.campaign import CampaignGrid, DeploymentCache, run_campaign
from repro.core.routing import (HET_SLOW_RATE, DetourTopology, HetMesh2D,
                                Mesh2D, Systolic2D, Topology, Torus2D,
                                available_topologies, build_topology,
                                get_topology, mesh_mean_degree,
                                parse_topology_spec, register_topology,
                                topology_spec)
from repro.distributed.telemetry import PodSimulator, PodTelemetryConfig

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

DATA = pathlib.Path(__file__).parent / "data"

# every registered builtin, instantiated at a couple of shapes
FABRICS = [
    ("mesh", Mesh2D(4, 4)),
    ("mesh", Mesh2D(6, 3)),
    ("torus", Torus2D(4, 4)),
    ("torus", Torus2D(5, 3)),
    ("systolic", Systolic2D(4, 4)),
    ("systolic", Systolic2D(8, 8)),
    ("het", HetMesh2D(4, 4, "fast2slow1")),
]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtins_registered():
    assert set(available_topologies()) >= {"mesh", "torus", "systolic",
                                           "het"}
    assert get_topology("mesh") is Mesh2D
    assert get_topology("torus") is Torus2D
    assert get_topology("systolic") is Systolic2D
    assert get_topology("het") is HetMesh2D


def test_get_topology_unknown_lists_options():
    with pytest.raises(KeyError, match="mesh"):
        get_topology("bogus")


def test_register_topology_rejects_collision_and_bad_key():
    with pytest.raises(ValueError, match="registered"):
        register_topology("mesh", Torus2D)
    with pytest.raises(ValueError, match="identifier"):
        register_topology("4x4", Torus2D)
    register_topology("mesh", Mesh2D, overwrite=True)   # explicit wins


def test_build_topology_variant():
    het = build_topology("het:fast2slow1", 4, 4)
    assert isinstance(het, HetMesh2D)
    assert het.rate_class[2] == HET_SLOW_RATE
    plain = build_topology("torus", 4, 4)
    assert isinstance(plain, Torus2D)


def test_parse_topology_spec():
    assert parse_topology_spec(4) == ("mesh", 4, 4)
    assert parse_topology_spec((6, 3)) == ("mesh", 6, 3)
    assert parse_topology_spec("6x3") == ("mesh", 6, 3)
    assert parse_topology_spec("torus:8x8") == ("torus", 8, 8)
    assert parse_topology_spec("het:4x4:fast2slow1") == \
        ("het:fast2slow1", 4, 4)
    for bad in ("4x4x4", "bogus:4x4", "het:4x4:fast0slow0", 0,
                (4, 4, 4)):
        with pytest.raises((ValueError, KeyError)):
            parse_topology_spec(bad)


def test_topology_spec_round_trip():
    for spec in ("mesh:4x4", "torus:8x8", "het:4x4:fast2slow1"):
        topo, w, h = parse_topology_spec(spec)
        assert topology_spec(topo, w, h) == spec


# ---------------------------------------------------------------------------
# per-topology routing contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,topo", FABRICS,
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v.width}x{v.height}")
def test_link_id_bijection(name, topo):
    assert topo.n_links == len(topo.links)
    assert len(set(topo.links)) == topo.n_links
    for lid, (u, v) in enumerate(topo.links):
        assert u != v
        assert topo.link_id(u, v) == lid


@pytest.mark.parametrize("name,topo", FABRICS,
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v.width}x{v.height}")
def test_routes_walk_links_from_src_to_dst(name, topo):
    n = topo.n_cores
    for src in range(n):
        for dst in range(0, n, 3):
            path = topo.route(src, dst)
            assert len(path) == topo.hops(src, dst)
            cur = src
            for lid in path:
                u, v = topo.links[lid]
                assert u == cur
                cur = v
            assert cur == dst


@pytest.mark.parametrize("name,topo", FABRICS,
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v.width}x{v.height}")
def test_links_of_router_matches_brute_force(name, topo):
    for core in range(topo.n_cores):
        expect = sorted(lid for lid, (u, v) in enumerate(topo.links)
                        if u == core or v == core)
        assert topo.links_of_router(core) == expect


@pytest.mark.parametrize("name,topo", FABRICS,
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v.width}x{v.height}")
def test_route_avoiding_deterministic_and_honoured(name, topo):
    src, dst = 0, topo.n_cores - 1
    avoid = set(topo.route(src, dst)[:1])
    first = topo.route_avoiding(src, dst, avoid)
    assert first == topo.route_avoiding(src, dst, avoid)
    if first is not None:
        assert not (set(first) & avoid)
        cur = src
        for lid in first:
            u, v = topo.links[lid]
            assert u == cur
            cur = v
        assert cur == dst


def _detour_over_a_base_route():
    base = Torus2D(4, 4)
    return DetourTopology(base, base.route(0, 10)[:1])


@pytest.mark.parametrize("name,topo",
                         FABRICS + [("detour", _detour_over_a_base_route())],
                         ids=lambda v: v if isinstance(v, str)
                         else f"{v.width}x{v.height}")
def test_path_links_are_the_nonzeros_of_path_matrix(name, topo):
    pairs = [(s, d) for s in range(topo.n_cores)
             for d in range(topo.n_cores)]
    rows, cols = topo.path_links(pairs)
    assert rows.dtype == cols.dtype == np.int64
    want_rows, want_cols = np.nonzero(topo.path_matrix(pairs))
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(cols, want_cols)
    assert len(set(zip(rows.tolist(), cols.tolist()))) == len(rows)
    # the routes it lists are this fabric's own, detours included
    i = pairs.index((0, 10))
    assert cols[rows == i].tolist() == sorted(set(topo.route(0, 10)))
    if name == "detour":
        assert not set(cols[rows == i].tolist()) & topo.avoid


def test_torus_wrap_distances():
    t = Torus2D(6, 6)
    # edge-to-edge neighbours are one wrap hop apart
    assert t.hops(t.core_id(0, 0), t.core_id(5, 0)) == 1
    assert t.hops(t.core_id(0, 0), t.core_id(0, 5)) == 1
    assert t.hops(t.core_id(0, 0), t.core_id(3, 3)) == 6
    # never worse than the mesh distance
    m = Mesh2D(6, 6)
    for src in range(36):
        for dst in range(36):
            assert t.hops(src, dst) <= m.hops(src, dst)


def test_systolic_unidirectional_with_wrap():
    s = Systolic2D(4, 4)
    for u, v in s.links:
        ux, uy = s.coords(u)
        vx, vy = s.coords(v)
        east = vy == uy and vx == (ux + 1) % 4
        south = vx == ux and vy == (uy + 1) % 4
        assert east or south
    # going "west" costs W-1 eastward hops (drain + edge re-injection)
    assert s.hops(s.core_id(1, 0), s.core_id(0, 0)) == 3


def test_mesh_mean_degree_matches_topology():
    for w, h in ((4, 4), (6, 3), (12, 8)):
        assert Mesh2D(w, h).mean_degree() == \
            pytest.approx(mesh_mean_degree(w, h))
    assert Torus2D(4, 4).mean_degree() > mesh_mean_degree(4, 4)


def test_het_rate_class_pattern():
    het = HetMesh2D(6, 1, "fast2slow1")
    assert het.rate_class.tolist() == [1.0, 1.0, HET_SLOW_RATE] * 2
    assert np.all(Mesh2D(4, 4).rate_class == 1.0)
    with pytest.raises(ValueError, match="pattern"):
        HetMesh2D(4, 4, "fast0slow0")


def test_detour_topology_wraps_any_fabric():
    base = Torus2D(4, 4)
    avoid = {base.route(0, 5)[0]}
    det = DetourTopology(base, avoid)
    assert det.n_cores == base.n_cores          # delegation
    path = det.route(0, 5)
    assert not (set(path) & avoid)
    assert det.path_matrix([(0, 5)]).shape == (1, base.n_links)


def test_base_topology_is_abstract():
    with pytest.raises(NotImplementedError):
        Topology(2, 2)


# ---------------------------------------------------------------------------
# campaign threading
# ---------------------------------------------------------------------------

def test_cross_topology_campaign_with_reroute():
    grid = CampaignGrid(workloads=("darknet19",),
                        meshes=("4x4", "torus:4x4", "systolic:8x8"),
                        kinds=("core", "link", "none"),
                        severities=(10.0,), reps=1, campaign_seed=3)
    res = run_campaign(grid, workers=0, detectors=("sloth",),
                       mitigation=("reroute",), cache=DeploymentCache())
    assert len(res.outcomes) == 3 * 3
    for o in res.outcomes:
        assert o.detector_results       # judged verdicts on every fabric
        assert o.topology in ("mesh", "torus", "systolic")
    table = res.by_topology()
    assert set(table) == {"mesh:4x4", "torus:4x4", "systolic:8x8"}
    for m in table.values():
        assert m.accuracy.trials == 2 and m.fpr.trials == 1
    # reroute acts on the torus core failure (material compute gap)
    torus_mit = [m for o in res.outcomes
                 if o.topology == "torus" and o.kind == "core"
                 for m in o.mitigation_results]
    assert any(m.acted for m in torus_mit)
    assert "torus:4x4" in res.summary()


def test_topology_cell_and_deploy_keys():
    grid = CampaignGrid(workloads=("darknet19",),
                        meshes=("4x4", "torus:4x4"), kinds=("none",),
                        severities=(8.0,), reps=1, campaign_seed=1)
    res = run_campaign(grid, workers=0, cache=DeploymentCache())
    cells = set(res.cells)
    assert ("darknet19", 4, 4, "none", 0.0, 0, "mesh") in cells
    assert ("darknet19", 4, 4, "none", 0.0, 0, "torus") in cells
    assert ("darknet19", "torus", 4, 4) in res.probe_overheads


def test_healthy_fpr_within_five_points_of_mesh():
    """Acceptance: re-derived thresholds keep healthy-fabric false-flag
    rates on torus/systolic within 5 points of the mesh baseline."""
    fprs = {}
    for spec in ("4x4", "torus:4x4", "systolic:4x4"):
        grid = CampaignGrid(workloads=("darknet19",), meshes=(spec,),
                            kinds=("none",), severities=(8.0,),
                            reps=5, campaign_seed=11)
        res = run_campaign(grid, workers=0, cache=DeploymentCache())
        label = next(iter(res.by_topology()))
        fprs[label] = res.metrics.fpr.rate
    assert fprs["torus:4x4"] <= fprs["mesh:4x4"] + 0.05
    assert fprs["systolic:4x4"] <= fprs["mesh:4x4"] + 0.05


def test_telemetry_pod_on_torus():
    """Both telemetry halves build their fabric through the registry
    from the one config field (the old code hard-coded Mesh2D twice)."""
    from repro.distributed.telemetry import PodDetector
    cfg = PodTelemetryConfig(mesh_w=4, mesh_h=4, topology="torus")
    pod = PodSimulator(cfg, step_flops=1e9, collective_bytes=1e6)
    assert isinstance(pod.mesh, Torus2D)
    det = PodDetector(cfg)
    assert isinstance(det.mesh, Torus2D)
    assert det.mesh.n_links == pod.mesh.n_links


# ---------------------------------------------------------------------------
# bit-identity regression vs the pre-refactor snapshot
# ---------------------------------------------------------------------------

def test_default_mesh_campaign_bit_identical_to_snapshot():
    """The snapshot in tests/data/ was captured from the pre-topology
    codebase; default W×H mesh campaigns must reproduce it bit for bit
    (same RNG streams, thresholds, verdicts, mitigation outcomes)."""
    base = json.loads((DATA / "mesh_campaign_baseline.json").read_text())
    g = base["grid"]
    grid = CampaignGrid(workloads=tuple(g["workloads"]),
                        meshes=tuple(tuple(m) for m in g["meshes"]),
                        kinds=tuple(g["kinds"]),
                        severities=tuple(g["severities"]),
                        n_failures=tuple(g["n_failures"]),
                        reps=g["reps"],
                        campaign_seed=g["campaign_seed"])
    res = run_campaign(grid, workers=0, executor="thread",
                       detectors=("sloth",),
                       mitigation=("reroute", "remap"),
                       cache=DeploymentCache())
    assert len(res.outcomes) == len(base["outcomes"])
    for o, b in zip(res.outcomes, base["outcomes"]):
        assert o.sim_seed == b["sim_seed"]
        assert list(o.truth_locations) == b["truth_locations"]
        assert list(o.truth_t0s) == b["truth_t0s"]
        assert list(o.truth_durations) == b["truth_durations"]
        assert o.compression_ratio == b["compression_ratio"]
        for r, br in zip(o.detector_results, b["detectors"]):
            assert r.flagged == br["flagged"]
            assert r.pred_kind == br["pred_kind"]
            assert r.pred_location == br["pred_location"]
            assert r.score == br["score"]           # exact float bits
            assert r.matched == br["matched"]
            assert r.truth_rank == br["truth_rank"]
            assert list(r.truth_ranks) == br["truth_ranks"]
        for m, bm in zip(o.mitigation_results, b["mitigation"]):
            assert m.policy == bm["policy"]
            assert m.acted == bm["acted"]
            assert m.correct == bm["correct"]
            assert list(m.exclude_cores) == bm["exclude_cores"]
            assert list(m.avoid_links) == bm["avoid_links"]
            assert m.healthy_time == bm["healthy_time"]
            assert m.failed_time == bm["failed_time"]
            assert m.mitigated_time == bm["mitigated_time"]


def test_lint_self_test_covers_topology_shape():
    from repro.analysis.lints import self_test
    self_test()
