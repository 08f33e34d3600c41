"""Streaming detection: chunked-vs-one-shot recorder parity, streamed
verdict ≡ post-hoc verdict, detection latency, the campaign streaming
axis, pod-telemetry regressions and the serving engine's split timing
series."""

import dataclasses
import math

import numpy as np
import pytest

from repro.core.campaign import CampaignGrid, run_campaign
from repro.core.failures import FailSlow
from repro.core.graph import build_workload
from repro.core.metrics import (DetectorOutcome, ScenarioOutcome,
                                detection_latency_stats)
from repro.core.recorder import record
from repro.core.routing import Mesh2D
from repro.core.sloth import Sloth, SlothConfig
from repro.core.streaming import StreamingRecorder, split_sim
from repro.distributed.telemetry import (PodDetector, PodSimulator,
                                         PodTelemetryConfig, StepTelemetry)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

ONSET = 1.0    # injected failure onset used by the module deployment


@pytest.fixture(scope="module")
def deployment():
    sloth = Sloth(build_workload("darknet19"), Mesh2D(4))
    sim = sloth.run([FailSlow("core", 5, ONSET, 8.0, 10.0)], seed=0)
    return sloth, sim


# ---------------------------------------------------------------------------
# split_sim: chunk concatenation must reproduce the exact record order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_chunks", (1, 5, 64))
def test_split_sim_preserves_record_order(deployment, n_chunks):
    """The sketch is order-sensitive, so chunking must be a pure
    partition of the original row order (64 chunks forces empty ones)."""
    _, sim = deployment
    chunks = split_sim(sim, n_chunks)
    assert len(chunks) == n_chunks
    for side in ("comp", "comm"):
        orig = getattr(sim, side)
        for k, v in orig.items():
            cat = np.concatenate(
                [np.asarray(getattr(c, side)[k]) for c in chunks])
            np.testing.assert_array_equal(cat, np.asarray(v))
    clocks = [c.total_time for c in chunks]
    assert clocks == sorted(clocks)          # stream clock is monotone
    assert clocks[-1] <= sim.total_time + 1e-9


# ---------------------------------------------------------------------------
# StreamingRecorder ≡ one-shot record, per impl
# ---------------------------------------------------------------------------

def _stream_over(sim, params, hop, impl, n_chunks):
    sr = StreamingRecorder(params, hop_latency=hop, impl=impl)
    for c in split_sim(sim, n_chunks):
        sr.observe(c)
    return sr.output()


@pytest.mark.parametrize("impl", ("ref", "batched"))
def test_streaming_recorder_matches_one_shot(deployment, impl):
    """Same impl, any chunking → bit-identical patterns and accounting
    (the chunks feed the identical record sequence through the identical
    sketch, and partial-pattern merging is associative)."""
    sloth, sim = deployment
    hop = sloth.sim_cfg.hop_latency
    one = record(sim, sloth.cfg.sketch, hop_latency=hop, impl=impl)
    out = _stream_over(sim, sloth.cfg.sketch, hop, impl, 5)
    assert out.comp_patterns == one.comp_patterns
    assert out.comm_patterns == one.comm_patterns
    assert (out.n_comp_records, out.n_comm_records) \
        == (one.n_comp_records, one.n_comm_records)
    assert (out.n_comp_drained, out.n_comm_drained) \
        == (one.n_comp_drained, one.n_comm_drained)
    assert (out.sketch_comp_bytes, out.sketch_comm_bytes) \
        == (one.sketch_comp_bytes, one.sketch_comm_bytes)
    assert (out.raw_comp_bytes, out.raw_comm_bytes) \
        == (one.raw_comp_bytes, one.raw_comm_bytes)
    assert out.compression_ratio == one.compression_ratio


@pytest.mark.parametrize("impl", ("ref", "batched"))
def test_streaming_recorder_parity_under_eviction(deployment, impl):
    """Tiny Stage-2 (L=8 ≪ distinct patterns): the per-chunk drained
    partials must fold into exactly the one-shot eviction stream."""
    from repro.core.sketch import SketchParams
    sloth, sim = deployment
    hop = sloth.sim_cfg.hop_latency
    p = SketchParams(d=2, m=256, H=4, L=8)
    one = record(sim, p, hop_latency=hop, impl=impl)
    out = _stream_over(sim, p, hop, impl, 7)
    assert one.n_comp_drained > 0 and one.n_comm_drained > 0
    assert out.comp_patterns == one.comp_patterns
    assert out.comm_patterns == one.comm_patterns
    assert (out.n_comp_drained, out.n_comm_drained) \
        == (one.n_comp_drained, one.n_comm_drained)
    assert out.compression_ratio == one.compression_ratio


def test_streaming_recorder_unknown_impl_rejected(deployment):
    sloth, _ = deployment
    with pytest.raises(ValueError, match="unknown recorder impl"):
        StreamingRecorder(sloth.cfg.sketch, impl="vectorised")


# ---------------------------------------------------------------------------
# SlothStream: streamed final verdict ≡ post-hoc analyse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ("ref", "batched"))
def test_stream_analyse_matches_post_hoc(deployment, impl):
    sloth, sim = deployment
    s = sloth if impl == "ref" else Sloth(
        sloth.graph, sloth.mesh, cfg=SlothConfig(recorder_impl=impl))
    post = s.analyse(sim)
    v, first_flag = s.stream_analyse(sim, n_chunks=5)
    assert (v.flagged, v.kind, v.location) \
        == (post.flagged, post.kind, post.location)
    assert v.ranking == post.ranking         # scores included: bit-equal
    assert v.recorder.compression_ratio \
        == post.recorder.compression_ratio
    assert post.flagged and first_flag is not None


def test_stream_detection_latency_known_onset(deployment):
    """A decisive failure injected at a known onset must be flagged
    mid-stream, and the latency must be the first flagged chunk's
    stream clock minus that onset."""
    sloth, sim = deployment
    st = sloth.stream()
    chunks = split_sim(sim, 6)
    flag_clock = None
    for i, c in enumerate(chunks):
        horizon = sim.total_time if i == len(chunks) - 1 else None
        v = st.observe(c, total_time=horizon)
        if v.flagged and flag_clock is None:
            flag_clock = sim.total_time if horizon is not None \
                else st.recorder.elapsed
    assert st.first_flag_time == flag_clock
    lat = st.detection_latency(ONSET)
    assert math.isfinite(lat) and lat > 0.0
    assert lat == st.first_flag_time - ONSET
    # flagged before the trace ended: streaming beats post-hoc to the
    # verdict by a nonzero margin
    assert st.first_flag_time < sim.total_time


def test_stream_detection_latency_inf_when_healthy(deployment):
    sloth, _ = deployment
    healthy = sloth.run([], seed=3)
    st = sloth.stream()
    for c in split_sim(healthy, 4):
        st.observe(c)
    assert st.first_flag_time is None
    assert st.detection_latency(0.0) == math.inf
    assert not any(v.flagged for v in st.verdicts)


# ---------------------------------------------------------------------------
# campaign streaming axis
# ---------------------------------------------------------------------------

def test_campaign_streaming_axis():
    """streaming=N must leave every judged field identical to the
    post-hoc campaign and attach latencies with the documented
    semantics: None on negatives, finite iff flagged on positives."""
    grid = CampaignGrid(workloads=("darknet19",), meshes=(4,),
                        kinds=("core", "none"), severities=(10.0,),
                        n_failures=(1,), reps=1, campaign_seed=0)
    res_s = run_campaign(grid, streaming=3)
    res_p = run_campaign(grid)
    judged = lambda d: (d.detector, d.flagged, d.pred_kind,  # noqa: E731
                        d.pred_location, d.matched, d.truth_rank,
                        d.truth_ranks)
    for s, p in zip(res_s.outcomes, res_p.outcomes):
        for ds, dp in zip(s.detector_results, p.detector_results):
            assert judged(ds) == judged(dp)
            assert dp.detection_latency is None     # post-hoc: no latency
            if s.kind == "none":
                assert ds.detection_latency is None
            else:
                assert ds.detection_latency is not None
                assert math.isfinite(ds.detection_latency) == ds.flagged
    assert res_s.metrics.detection is not None
    assert res_p.metrics.detection is None
    assert "detection latency" in res_s.summary()
    assert "detection latency" not in res_p.summary()


def test_campaign_streaming_validation():
    grid = CampaignGrid(workloads=("darknet19",), meshes=(4,),
                        kinds=("core",), severities=(10.0,),
                        n_failures=(1,), reps=1, campaign_seed=0)
    with pytest.raises(ValueError, match="streaming"):
        run_campaign(grid, streaming=-2)


def test_detection_latency_stats_reduction():
    """Unit-level reduction semantics: negatives and non-streamed
    outcomes are excluded, inf counts as streamed-but-missed, and the
    mean/p95 summarise only the finite latencies."""
    def scen(i, kind, lat, flagged):
        d = DetectorOutcome(
            detector="sloth", flagged=flagged,
            pred_kind="core" if flagged else None,
            pred_location=0 if flagged else None, score=1.0,
            matched=flagged, truth_rank=1 if flagged else None,
            detection_latency=lat)
        return ScenarioOutcome(
            scenario_id=i, workload="w", mesh_w=4, mesh_h=4, kind=kind,
            severity=0.0 if kind == "none" else 10.0,
            n_failures=0 if kind == "none" else 1, rep=0, sim_seed=0,
            truth_locations=(), truth_t0s=(), truth_durations=(),
            detector_results=(d,), compression_ratio=1.0,
            total_time=1.0, probe_overhead=0.0)

    outs = [scen(0, "core", 2.0, True), scen(1, "core", math.inf, False),
            scen(2, "none", None, False), scen(3, "core", 4.0, True)]
    st = detection_latency_stats(outs)
    assert (st.n_measured, st.n_detected) == (3, 2)
    assert st.mean == pytest.approx(3.0)
    assert 2.0 <= st.p95 <= 4.0
    # a campaign that never streamed reports no latency block at all
    assert detection_latency_stats([scen(0, "core", None, True)]) is None


# ---------------------------------------------------------------------------
# pod telemetry: step-gap regression, impl plumbing, live windows
# ---------------------------------------------------------------------------

def test_pod_step_gap_uses_slowest_link():
    """Regression: the all-reduce barrier used to wait only on the *last
    enumerated* link (``max`` over a one-element list), so a slow
    non-last link never delayed the next step."""
    cfg = PodTelemetryConfig(mesh_w=2, mesh_h=2, window_steps=4)
    pod = PodSimulator(cfg, step_flops=1e12, collective_bytes=1e8, seed=0)
    assert pod.mesh.n_links > 1
    pod.inject(FailSlow("link", 0, 0.0, 1e9, 100.0))   # NOT the last link
    sim = pod.run_steps(2)
    nl, nc = pod.mesh.n_links, pod.mesh.n_cores
    arrive0 = float(np.max(np.asarray(sim.comm["t_arrive"])[:nl]))
    start1 = float(np.min(np.asarray(sim.comp["t_start"])[nc:]))
    assert start1 >= arrive0 - 1e-12


def test_pod_detector_recorder_impl_plumbing():
    """PodTelemetryConfig.recorder_impl reaches the recorder (the pod
    detector used to hard-code impl='ref') and both impls agree."""
    cfg_r = PodTelemetryConfig(mesh_w=4, mesh_h=4, window_steps=16)
    cfg_b = dataclasses.replace(cfg_r, recorder_impl="batched")
    pod = PodSimulator(cfg_r, step_flops=5e12, collective_bytes=4e9,
                       seed=1)
    pod.inject(FailSlow("core", 5, 0.0, 1e9, 10.0))
    sim = pod.run_steps(16)
    va = PodDetector(cfg_r).analyse(sim)
    vb = PodDetector(cfg_b).analyse(sim)
    assert va.flagged and (va.kind, va.location) == ("core", 5)
    assert (va.flagged, va.kind, va.location) \
        == (vb.flagged, vb.kind, vb.location)


def test_pod_detector_observe_streams_windows():
    """observe() holds sketch state across windows: streaming the trace
    window-by-window reaches the same localisation as post-hoc
    analyse(), and the failure is flagged before the last window."""
    cfg = PodTelemetryConfig(mesh_w=4, mesh_h=4, window_steps=8)
    pod = PodSimulator(cfg, step_flops=5e12, collective_bytes=4e9,
                       seed=1)
    pod.inject(FailSlow("core", 5, 0.0, 1e9, 10.0))
    sim = pod.run_steps(24)
    post = PodDetector(cfg).analyse(sim)
    det = PodDetector(cfg)
    verdicts = [det.observe(c) for c in split_sim(sim, 3)]
    assert (verdicts[-1].flagged, verdicts[-1].kind,
            verdicts[-1].location) == (post.flagged, post.kind,
                                       post.location) == (True, "core", 5)
    assert verdicts[0].flagged          # detected in the first window


def test_step_telemetry_flags_injected_slow_host():
    """The live bridge: measured step times with a 10× slow burst must
    flag the local host (chip 0) and stay core-localised."""
    telem = StepTelemetry(seed=0)
    rng = np.random.default_rng(0)
    for step in range(25):
        dt = 0.05 * (1 + 0.01 * abs(rng.standard_normal()))
        if 10 <= step < 18:
            dt *= 10.0
        telem.record_step(dt)
    telem.flush()
    assert telem.flagged
    flagged = [v for v in telem.verdicts if v.flagged]
    assert all((v.kind, v.location) == ("core", 0) for v in flagged)
    assert telem.plans[-1]["action"] != "none" or not \
        telem.verdicts[-1].flagged


def test_step_telemetry_clean_loop_stays_silent():
    telem = StepTelemetry(seed=0)
    rng = np.random.default_rng(1)
    for _ in range(25):
        telem.record_step(0.05 * (1 + 0.01 * abs(rng.standard_normal())))
    telem.flush()
    assert telem.verdicts and not telem.flagged
    assert all(p["action"] == "none" for p in telem.plans)


def test_step_telemetry_warmup_discards_compile_step():
    """The first (jit-compile) step is orders slower than steady state;
    warmup must keep it out of both the baseline and the windows."""
    telem = StepTelemetry(warmup=1, seed=0)
    telem.record_step(30.0)              # compile step
    for _ in range(telem.cfg.window_steps):
        telem.record_step(0.05)
    assert telem.verdicts and not telem.flagged


# ---------------------------------------------------------------------------
# serving engine: split prefill/decode series + step hook
# ---------------------------------------------------------------------------

def test_engine_split_timing_series():
    """Regression: p50/p99 'decode' percentiles were computed over one
    series interleaving prefill and decode steps with only index 0
    dropped, so every later batch's prefill polluted the decode
    distribution."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, Request, ServeEngine

    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    events = []
    engine = ServeEngine(
        cfg, params, EngineConfig(batch=2, cache_len=32),
        step_hook=lambda kind, dt: events.append((kind, dt)))
    rng = np.random.default_rng(0)
    for i in range(3):                   # 2 batches at batch=2
        engine.submit(Request(i, rng.integers(0, cfg.vocab, size=4)
                              .astype(np.int32), max_new=3))
    done = engine.run()
    assert len(done) == 3
    assert len(engine.prefill_times) == 2
    assert len(engine.decode_times) == 2 * 3
    assert [k for k, _ in events] \
        == ["prefill"] + ["decode"] * 3 + ["prefill"] + ["decode"] * 3
    assert engine.decode_times == [dt for k, dt in events if k == "decode"]
    assert engine.prefill_times \
        == [dt for k, dt in events if k == "prefill"]


class _Pending:
    """A step result whose ``block_until_ready`` waits, as a device
    result does while its step still runs on the chip."""

    def __init__(self, value, wait_s):
        self.value = value
        self.wait_s = wait_s

    def block_until_ready(self):
        import time
        time.sleep(self.wait_s)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype=dtype)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_engine_step_timing_covers_device_wait(kind):
    """Regression: the engine read the clock right after dispatching a
    step, so on a TPU ``decode_times`` (and the serve ``--telemetry``
    signal) timed the enqueue.  Each timing must end only once the
    step's next tokens are ready."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, Request, ServeEngine

    wait = 0.05
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    events = []
    engine = ServeEngine(
        cfg, params, EngineConfig(batch=2, cache_len=32),
        step_hook=lambda k, dt: events.append((k, dt)))
    for name in ("prefill", "decode"):
        def wrapped(p, toks, *rest, step=getattr(engine, "_" + name),
                    slow=name == kind):
            out = step(p, np.asarray(toks), *rest)
            return ((_Pending(out[0], wait) if slow else out[0]),) \
                + tuple(out[1:])
        setattr(engine, "_" + name, wrapped)
    for _ in range(2):              # the second round runs compiled
        events.clear()
        for i in range(2):
            engine.submit(Request(i, np.arange(1, 5, dtype=np.int32),
                                  max_new=2))
        assert len(engine.run()) == 2
    timed = [dt for k, dt in events if k == kind]
    assert timed and min(timed) >= wait
