"""Recorder pipeline: pattern-key space disjointness, ref vs batched
recorder parity (patterns, drained streams, compression accounting) and
the campaign-level impl plumbing."""

import numpy as np
import pytest

from repro.core import probes as P
from repro.core.failures import FailSlow
from repro.core.graph import build_workload
from repro.core.recorder import record
from repro.core.routing import Mesh2D
from repro.core.sloth import Sloth, SlothConfig

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# ---------------------------------------------------------------------------
# pattern-key spaces
# ---------------------------------------------------------------------------

def test_comp_comm_key_tags_distinct():
    """Regression: the comm tag was written ``2 << 61`` — which *is*
    ``1 << 62``, the comp tag — so the type-disambiguation bit was
    identical for both key spaces."""
    assert P.COMP_KEY_TAG != P.COMM_KEY_TAG
    assert P.COMP_KEY_TAG & P.COMM_KEY_TAG == 0
    # both tags must sit in int64 sign-free territory
    assert 0 < P.COMM_KEY_TAG < P.COMP_KEY_TAG < 2**63


def test_comp_comm_key_spaces_cannot_collide():
    """The historical aliasing example: comp(core=5, stage=1, op=0,
    fb=0) and comm(src=5, dst=1, stage=0, vb=0) packed to the same 64
    bits under the colliding tags.  With distinct tag bits, no comp key
    can equal any comm key."""
    comp = {"core": np.array([5]), "stage": np.array([1]),
            "op": np.array([0]), "flops": np.array([1.0])}
    comm = {"src": np.array([5]), "dst": np.array([1]),
            "stage": np.array([0]), "bytes": np.array([1.0])}
    ck = int(P.comp_pattern_keys(comp)[0])
    mk = int(P.comm_pattern_keys(comm)[0])
    # the payload bits still alias (that is what made the bug silent) …
    assert ck & ~(P.COMP_KEY_TAG | P.COMM_KEY_TAG) \
        == mk & ~(P.COMP_KEY_TAG | P.COMM_KEY_TAG)
    # … so only the tag bits keep the spaces apart
    assert ck != mk

    rng = np.random.default_rng(0)
    n = 500
    comp = {"core": rng.integers(0, 256, n), "stage": rng.integers(0, 64, n),
            "op": rng.integers(0, 8, n),
            "flops": rng.uniform(1, 2**50, n)}
    comm = {"src": rng.integers(0, 256, n), "dst": rng.integers(0, 256, n),
            "stage": rng.integers(0, 64, n),
            "bytes": rng.uniform(1, 2**50, n)}
    assert not set(P.comp_pattern_keys(comp).tolist()) \
        & set(P.comm_pattern_keys(comm).tolist())


def test_decoders_unaffected_by_tag_fix():
    comp = {"core": np.array([7]), "stage": np.array([3]),
            "op": np.array([2]), "flops": np.array([1e6])}
    d = P.decode_comp_key(int(P.comp_pattern_keys(comp)[0]))
    assert (d["core"], d["stage"], d["op"]) == (7, 3, 2)
    comm = {"src": np.array([4]), "dst": np.array([9]),
            "stage": np.array([5]), "bytes": np.array([4096.0])}
    d = P.decode_comm_key(int(P.comm_pattern_keys(comm)[0]))
    assert (d["src"], d["dst"], d["stage"]) == (4, 9, 5)


# ---------------------------------------------------------------------------
# ref vs batched recorder parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deployment():
    sloth = Sloth(build_workload("darknet19"), Mesh2D(4))
    sim = sloth.run([FailSlow("core", 5, 1.0, 8.0, 10.0)], seed=0)
    return sloth, sim


def _assert_recorder_parity(a, b):
    for side in ("comp", "comm"):
        pa = {p.key: p for p in getattr(a, side + "_patterns")}
        pb = {p.key: p for p in getattr(b, side + "_patterns")}
        assert set(pa) == set(pb), side
        for k in pa:
            assert pa[k].count == pb[k].count, (side, k)
            assert pa[k].arrival == pb[k].arrival, (side, k)
            assert pa[k].sum_dur == pytest.approx(pb[k].sum_dur, rel=1e-4)
            assert pa[k].min_dur == pytest.approx(pb[k].min_dur, rel=1e-5)
    assert a.sketch_comp_bytes == b.sketch_comp_bytes
    assert a.sketch_comm_bytes == b.sketch_comm_bytes
    assert (a.n_comp_drained, a.n_comm_drained) \
        == (b.n_comp_drained, b.n_comm_drained)
    assert (a.n_comp_records, a.n_comm_records) \
        == (b.n_comp_records, b.n_comm_records)
    assert a.compression_ratio == b.compression_ratio


def test_record_impl_parity(deployment):
    """record(impl='batched') reproduces the numpy-oracle patterns (keys,
    counts, arrival order; stats to f32 tolerance) and byte-identical
    compression accounting on a real instrumented trace."""
    sloth, sim = deployment
    hop = sloth.sim_cfg.hop_latency
    a = record(sim, sloth.cfg.sketch, hop_latency=hop, impl="ref")
    b = record(sim, sloth.cfg.sketch, hop_latency=hop, impl="batched")
    assert a.impl == "ref" and b.impl == "batched"
    _assert_recorder_parity(a, b)
    # comp keys carry the tag bit the sketch's 31-bit halves truncate —
    # the batched path must restore it, or the key spaces re-collide
    assert all(p.key & P.COMP_KEY_TAG for p in b.comp_patterns)
    assert all(p.key & P.COMM_KEY_TAG for p in b.comm_patterns)


def test_record_impl_parity_under_eviction(deployment):
    """Same parity with a tiny Stage-2 (L=8 ≪ distinct patterns): both
    paths must drain the same FIFO victims and account their bytes in
    the compressed stream identically."""
    from repro.core.sketch import SketchParams
    sloth, sim = deployment
    hop = sloth.sim_cfg.hop_latency
    p = SketchParams(d=2, m=256, H=4, L=8)
    a = record(sim, p, hop_latency=hop, impl="ref")
    b = record(sim, p, hop_latency=hop, impl="batched")
    assert a.n_comp_drained > 0 and a.n_comm_drained > 0
    _assert_recorder_parity(a, b)


@pytest.mark.parametrize("impl", ["ref", "batched"])
def test_recorder_reports_state_device(deployment, impl):
    """RecorderOutput.device names where the sketch state lived — the
    backend's device for the batched path (one-shot and streaming), the
    host for the numpy oracle — so a chip run can prove the recorder ran
    on the chip."""
    import jax

    from repro.core.streaming import StreamingRecorder, split_sim
    sloth, sim = deployment
    hop = sloth.sim_cfg.hop_latency
    want = jax.default_backend() if impl == "batched" else "host"
    assert record(sim, sloth.cfg.sketch, hop_latency=hop,
                  impl=impl).device == want
    stream = StreamingRecorder(sloth.cfg.sketch, hop_latency=hop, impl=impl)
    assert stream.output().device == "host"      # nothing recorded yet
    for chunk in split_sim(sim, 2):
        stream.observe(chunk)
    assert stream.output().device == want


def test_record_unknown_impl_rejected(deployment):
    sloth, sim = deployment
    with pytest.raises(ValueError, match="unknown recorder impl"):
        record(sim, sloth.cfg.sketch, impl="vectorised")


def test_sloth_verdict_identical_across_recorder_impls(deployment):
    """End-to-end: analysing one trace with recorder_impl='batched'
    yields the same flag / kind / location / ranking order as the
    default oracle recorder."""
    sloth, sim = deployment
    va = sloth.analyse(sim)
    sloth_b = Sloth(sloth.graph, sloth.mesh,
                    cfg=SlothConfig(recorder_impl="batched"))
    vb = sloth_b.analyse(sim)
    assert (va.flagged, va.kind, va.location) \
        == (vb.flagged, vb.kind, vb.location)
    assert [(k, l) for k, l, _ in va.ranking] \
        == [(k, l) for k, l, _ in vb.ranking]
    assert va.recorder.compression_ratio == vb.recorder.compression_ratio


def test_campaign_recorder_impl_plumbing():
    """run_campaign(cfg=SlothConfig(recorder_impl='batched')) produces
    outcomes matching the default path verdict-for-verdict (scores are
    float-tolerance, so equality is on the judged fields), with
    bit-identical compression ratios."""
    from repro.core.campaign import CampaignGrid, DeploymentCache, \
        run_campaign
    grid = CampaignGrid(workloads=("darknet19",), meshes=(4,),
                        kinds=("core", "none"), severities=(10.0,),
                        reps=1, campaign_seed=7)
    res_a = run_campaign(grid, workers=0, cache=DeploymentCache())
    res_b = run_campaign(grid, workers=0, cache=DeploymentCache(),
                         cfg=SlothConfig(recorder_impl="batched"))
    assert len(res_a.outcomes) == len(res_b.outcomes) == 2
    for a, b in zip(res_a.outcomes, res_b.outcomes):
        assert a.compression_ratio == b.compression_ratio
        for da, db in zip(a.detector_results, b.detector_results):
            assert (da.flagged, da.pred_kind, da.pred_location,
                    da.matched, da.truth_rank) \
                == (db.flagged, db.pred_kind, db.pred_location,
                    db.matched, db.truth_rank)
