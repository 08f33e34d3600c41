"""The span/counter module (``repro.core.spans``) and the spans the
program opens where its work happens: the pod verdict and the training
step."""

import math

import numpy as np
import pytest

from repro.core import spans


@pytest.fixture
def fresh(monkeypatch):
    """Empty rings for the test, restored afterwards."""
    monkeypatch.setattr(spans, "_rings", {})
    return spans


def test_parent_and_root_ids_through_nesting(fresh):
    with spans.span("outer", root="step", tag="a") as outer:
        with spans.span("mid") as mid:
            with spans.span("inner", root="verdict") as inner:
                pass
        with spans.span("mid") as mid2:
            pass
    with spans.span("outer", root="step") as outer2:
        pass
    assert outer.parent is None and outer.attrs == {"tag": "a"}
    assert mid.parent == outer.seq and mid2.parent == outer.seq
    assert inner.parent == mid.seq
    step = outer.ids["step"]
    assert mid.ids == mid2.ids == {"step": step}
    assert inner.ids == {"step": step, "verdict": inner.ids["verdict"]}
    assert outer2.ids == {"step": step + 1}
    assert outer2.parent is None
    # a span closed by an exception still lands, and the stack unwinds
    with pytest.raises(KeyError):
        with spans.span("outer", root="step"):
            raise KeyError
    with spans.span("after") as after:
        pass
    assert after.parent is None
    assert len(spans.within("outer")) == 3


def test_counters_land_on_the_innermost_span(fresh):
    spans.count("loose", 2)                 # outside every span: dropped
    with spans.span("a") as a:
        spans.count("n", 3)
        with spans.span("b") as b:
            spans.count("n", 4)
            spans.count("n", 1)
        spans.count("m")
    assert a.counts == {"n": 3, "m": 1}
    assert b.counts == {"n": 5}
    assert spans.within("a") == [a] and spans.within("a")[0].counts == \
        {"n": 3, "m": 1}


def test_ring_bound_and_dropped_count(fresh, monkeypatch):
    monkeypatch.setattr(spans, "RING_SIZE", 4)
    t_start = spans.clock()
    made = []
    for _ in range(6):
        with spans.span("r") as s:
            made.append(s)
    assert spans.dropped("r") == 2
    # the window reaching back to the dropped spans cannot be counted
    assert spans.within("r", t_start) is None
    # a window starting after the last dropped span reads in full
    assert spans.within("r", made[2].t0) == made[2:]
    assert spans.within("r", made[2].t0, made[3].t0) == made[2:4]
    assert spans.within("never") == [] and spans.dropped("never") == 0


def test_span_times_are_monotone_on_the_clock(fresh):
    before = spans.clock()
    with spans.span("t") as s:
        assert math.isnan(s.t1)
        inside = spans.clock()
    after = spans.clock()
    assert before <= s.t0 <= inside <= s.t1 <= after
    assert s.seconds == s.t1 - s.t0 >= 0


def test_a_span_is_on_the_profilers_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData, ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with spans.span("spans.test.probe"):
        spans.count("n", 1)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert "spans.test.probe" in names


def _pod_windows(n_windows, slow_from):
    """Telemetry windows of a 4x4 pod, chip 5 10x slow from window
    ``slow_from`` on."""
    from repro.core.failures import FailSlow
    from repro.distributed.telemetry import PodSimulator, PodTelemetryConfig

    cfg = PodTelemetryConfig(mesh_w=4, mesh_h=4, window_steps=8)
    sim = PodSimulator(cfg, step_flops=1e12, collective_bytes=1e8, seed=3)
    out, t, step = [], 0.0, 0
    for i in range(n_windows):
        if i == slow_from:
            sim.inject(FailSlow("core", 5, t, 1e9, 10.0))
        w = sim.run_steps(8, t0=t, step0=step)
        t += float(w.total_time)
        step += 8
        out.append(w)
    return cfg, out


@pytest.mark.parametrize("impl", ["ref", "batched"])
def test_pod_verdict_span_tree(fresh, impl):
    import dataclasses

    from repro.distributed.telemetry import PodDetector

    cfg, windows = _pod_windows(3, slow_from=2)
    det = PodDetector(dataclasses.replace(cfg, recorder_impl=impl))
    t0 = spans.clock()
    verdicts = [det.observe(w) for w in windows]
    assert verdicts[-1].flagged and verdicts[-1].location == 5
    roots = spans.within("pod.verdict", t0)
    assert len(roots) == len(windows)
    assert [r.parent for r in roots] == [None] * len(windows)
    ids = [r.ids["verdict"] for r in roots]
    assert ids == list(range(ids[0], ids[0] + len(windows)))

    def kids(parent, name):
        return [s for s in spans.within(name, t0) if s.parent == parent.seq]

    last = roots[-1]
    assert len(kids(last, "sloth.record")) == 2      # observe, then output
    rec_seqs = {s.seq for s in kids(last, "sloth.record")}
    under = {n: [s for s in spans.within(n, t0) if s.parent in rec_seqs]
             for n in ("sloth.record.runs", "sloth.record.scan",
                       "sloth.record.decode")}
    assert [s.attrs["side"] for s in under["sloth.record.runs"]] \
        == ["comp", "comm"]
    assert [s.attrs["side"] for s in under["sloth.record.scan"]] \
        == ["comp", "comm"]
    assert len(under["sloth.record.decode"]) == 1
    for r, s in zip(under["sloth.record.runs"], under["sloth.record.scan"]):
        assert r.counts == {} and r.t1 <= s.t0
        assert s.counts["runs"] > 0 and "drained" in s.counts
    assert under["sloth.record.decode"][0].counts["patterns"] > 0
    cores, = kids(last, "sloth.detect.cores")
    assert cores.counts["patterns"] > 0
    links, = kids(last, "sloth.detect.links")
    assert links.counts == {"events": links.counts["events"]}
    assert links.counts["events"] > 0
    em, = kids(links, "sloth.detect.links.em")
    assert em.counts == {"path_links": em.counts["path_links"]}
    assert em.counts["path_links"] >= links.counts["events"]
    assert links.t0 <= em.t0 <= em.t1 <= links.t1
    fr, = kids(last, "sloth.failrank")
    for name in ("sloth.failrank.mcg", "sloth.failrank.iterate",
                 "sloth.failrank.attribute"):
        assert len(kids(fr, name)) == 1
    assert kids(fr, "sloth.failrank.iterate")[0].counts["iters"] > 0
    # a healthy verdict returns before FailRank
    assert kids(roots[0], "sloth.failrank") == []
    # every span of a verdict carries its id and sits inside it
    for name in ("sloth.record", "sloth.detect.cores", "sloth.detect.links",
                 "sloth.detect.links.em", "sloth.failrank.iterate"):
        for s in spans.within(name, t0):
            root = next(r for r in roots if r.ids == s.ids)
            assert root.t0 <= s.t0 <= s.t1 <= root.t1


def test_link_em_counts_its_route_entries(fresh):
    from repro.core.detection import detect_links
    from repro.core.routing import Mesh2D
    from repro.core.sketch import Pattern

    mesh = Mesh2D(4)
    rng = np.random.default_rng(1)
    ends = [(int(s), int(d)) for s, d in rng.integers(0, 16, (300, 2))]
    pats = [Pattern(key=s | d << 12, count=4, sum_dur=4e-6, sum_sq_dur=4e-12,
                    sum_val=4e5, t_first=i * 1e-6, t_last=i * 1e-6 + 5e-7,
                    arrival=i, min_dur=1e-6 * (1 + mesh.hops(s, d)))
            for i, (s, d) in enumerate(ends)]
    t0 = spans.clock()
    detect_links(pats, mesh, total_time=len(pats) * 1e-6)
    links, = spans.within("sloth.detect.links", t0)
    em, = spans.within("sloth.detect.links.em", t0)
    assert em.parent == links.seq
    inter = [e for e in ends if e[0] != e[1]]
    assert links.counts == {"events": len(inter)}
    rows, _ = mesh.path_links(inter)
    assert em.counts == {"path_links": len(rows)}
    assert len(rows) == sum(mesh.hops(s, d) for s, d in inter)


def test_telemetry_flush_spans(fresh):
    from repro.distributed.telemetry import StepTelemetry

    telem = StepTelemetry(seed=0)      # 4x4 pod, 8-step windows
    t0 = spans.clock()
    for _ in range(1 + 8):
        telem.record_step(0.1)
    flush, = spans.within("telemetry.flush", t0)
    assert flush.counts == {}
    for name in ("telemetry.flush.simulate", "pod.verdict",
                 "telemetry.flush.plan"):
        s, = spans.within(name, t0)
        assert s.parent == flush.seq
    assert telem.flush() is None       # nothing buffered: no span
    assert len(spans.within("telemetry.flush", t0)) == 1


def test_training_step_spans_and_the_telemetry_signal(fresh, monkeypatch):
    """One ``train.step`` per step with its children; ``StepTelemetry``
    gets the step's call to its loss on the host, as it did before the
    spans: ``train.dispatch`` start to ``train.sync`` end."""
    from repro.distributed.telemetry import StepTelemetry
    from repro.launch.train import main

    seen = []
    orig = StepTelemetry.record_step

    def record_step(self, dt):
        seen.append(dt)
        return orig(self, dt)

    monkeypatch.setattr(StepTelemetry, "record_step", record_step)
    t0 = spans.clock()
    losses = main(["--arch", "smollm-135m", "--smoke", "--steps", "6",
                   "--batch", "2", "--seq", "32", "--telemetry",
                   "--telemetry-window", "2", "--log-every", "2"])
    assert len(losses) == 6
    steps = spans.within("train.step", t0)
    assert len(steps) == 6
    ids = [s.ids["step"] for s in steps]
    assert ids == list(range(ids[0], ids[0] + 6))

    def child(step, name):
        return [s for s in spans.within(name, t0) if s.parent == step.seq]

    dts = []
    for step in steps:
        data, = child(step, "train.data")
        dispatch, = child(step, "train.dispatch")
        sync, = child(step, "train.sync")
        tele, = child(step, "train.telemetry")
        assert data.t1 <= dispatch.t0 <= dispatch.t1 <= sync.t0 <= sync.t1
        assert sync.t1 <= tele.t0
        dts.append(sync.t1 - dispatch.t0)
    assert seen == dts
    assert len(spans.within("train.log", t0)) == 4      # steps 0, 2, 4, 5
    assert len(spans.within("telemetry.flush", t0)) == 3
    assert spans.within("train.ckpt", t0) == []
    # the flush runs inside the step that completes its window
    flush = spans.within("pod.verdict", t0)[0]
    assert "step" in flush.ids and "verdict" in flush.ids


def test_engine_timings_read_the_one_clock(monkeypatch):
    """The engine's prefill and decode timings are differences of
    ``spans.clock`` readings."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving import engine as E

    ticks = iter(np.arange(0.0, 1000.0, 0.5))
    monkeypatch.setattr(E, "clock", lambda: float(next(ticks)))
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = E.ServeEngine(cfg, params, E.EngineConfig(batch=2, cache_len=32))
    eng.submit(E.Request(0, np.arange(1, 5, dtype=np.int32), max_new=2))
    eng.run()
    assert eng.prefill_times == [0.5]
    assert eng.decode_times == [0.5, 0.5]
    assert not hasattr(eng, "step_times")
