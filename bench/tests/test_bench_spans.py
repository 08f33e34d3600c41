"""The readers of the program's own spans (``bench/program_spans.py`` and
the per-layer files that use it), on rings planted with known times."""

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness as H  # noqa: E402
from repro.core import spans  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    """Empty rings, and a clock the test sets by hand."""
    monkeypatch.setattr(spans, "_rings", {})
    c = FakeClock()
    monkeypatch.setattr(spans, "clock", c)
    return c


def at(clock, t, name, body=None, **kw):
    """Span ``name`` from ``t``; ``body(clock)`` runs inside and leaves
    the clock where the span ends."""
    clock.t = t
    with spans.span(name, **kw) as s:
        if body:
            body(clock)
    return s


def plant_verdict(clock, t, cores, links, em, events=0):
    """One verdict at ``t``: cores, then links holding the EM and counting
    its ``events``; returns when it ends."""
    def verdict(c):
        def cores_body(c):
            c.t += cores

        def links_body(c):
            spans.count("events", events)
            at(c, c.t + 0.25, "sloth.detect.links.em",
               lambda c: setattr(c, "t", c.t + em))
            c.t += links - em - 0.25

        at(c, c.t + 0.5, "sloth.detect.cores", cores_body)
        at(c, c.t, "sloth.detect.links", links_body)
        c.t += 0.5
    return at(clock, t, "pod.verdict", verdict, root="verdict").t1


def run_for(t0, t1):
    return types.SimpleNamespace(t0=t0, t1=t1)


def read(metric, run):
    return H.reader_for(metric, BENCH).read(run)


def test_detector_readers_per_verdict(clock):
    end = plant_verdict(clock, 0.0, cores=9.0, links=99.0, em=90.0)
    # the window: two verdicts with known splits
    t = plant_verdict(clock, 1000.0, cores=1.0, links=10.0, em=6.0)
    plant_verdict(clock, t + 1.0, cores=3.0, links=20.0, em=14.0)
    run = run_for(end + 1.0, 10 ** 6)
    assert read("detect_cores_s.pod", run) == pytest.approx(2.0)
    assert read("em_s.pod", run) == pytest.approx(10.0)
    assert read("links_self_s.pod", run) == pytest.approx(5.0)
    # the three add up to the whole of detection
    total = sum(read(m, run) for m in
                ("detect_cores_s.pod", "em_s.pod", "links_self_s.pod"))
    assert total == pytest.approx((1 + 10 + 3 + 20) / 2)
    # nothing in the window: no number
    assert read("em_s.pod", run_for(10 ** 7, 10 ** 8)) is None


def test_em_events_per_verdict(clock):
    end = plant_verdict(clock, 0.0, cores=1.0, links=2.0, em=1.0,
                        events=10 ** 6)
    t = plant_verdict(clock, 100.0, cores=1.0, links=2.0, em=1.0,
                      events=300)
    plant_verdict(clock, t, cores=1.0, links=2.0, em=1.0, events=500)
    assert read("em_events.pod", run_for(end + 1.0, 10 ** 6)) \
        == pytest.approx(400.0)
    assert read("em_events.pod", run_for(10 ** 7, 10 ** 8)) is None


def test_host_ms_per_step(clock):
    def step(t, data, dispatch, sync):
        def body(c):
            at(c, c.t, "train.data", lambda c: setattr(c, "t", c.t + data))
            at(c, c.t, "train.dispatch",
               lambda c: setattr(c, "t", c.t + dispatch))
            at(c, c.t, "train.sync", lambda c: setattr(c, "t", c.t + sync))
        return at(clock, t, "train.step", body, root="step").t1

    step(0.0, 0.5, 0.5, 9.0)                     # before the window
    t = step(10.0, 0.002, 0.001, 0.08)
    t = step(t, 0.004, 0.001, 0.08)
    # the step the window closes in: its data span ends it, no dispatch
    clock.t = t
    with pytest.raises(StopIteration):
        with spans.span("train.step", root="step"):
            with spans.span("train.data"):
                raise StopIteration
    run = run_for(5.0, t + 1.0)
    assert read("host_ms_per_step.train", run) == pytest.approx(4.0)


def test_a_dropped_span_inside_the_window_reads_none(clock, monkeypatch):
    monkeypatch.setattr(spans, "RING_SIZE", 2)
    t = plant_verdict(clock, 0.0, cores=1.0, links=2.0, em=1.0)
    t = plant_verdict(clock, t, cores=1.0, links=2.0, em=1.0)
    plant_verdict(clock, t, cores=1.0, links=2.0, em=1.0)
    assert spans.dropped("pod.verdict") == 1
    assert read("detect_cores_s.pod", run_for(0.0, 100.0)) is None
    assert read("links_self_s.pod", run_for(0.0, 100.0)) is None
    # a window after the dropped verdict reads in full
    assert read("detect_cores_s.pod", run_for(t, 100.0)) \
        == pytest.approx(1.0)


def test_a_program_without_spans_reads_none(monkeypatch):
    """Without ``repro.core.spans``, as in a traced run of a program older
    than it, every reader leaves its metric out and does not raise."""
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    run = run_for(0.0, 100.0)
    for m in ("detect_cores_s.pod", "em_s.pod", "links_self_s.pod",
              "em_events.pod", "host_ms_per_step.train"):
        assert read(m, run) is None


def test_the_new_metrics_are_read_in_their_cells():
    pod = {"detect_cores_s.pod", "em_s.pod", "links_self_s.pod",
           "em_events.pod"}
    for cell, want in (("pod16-slowchip", pod), ("pod16-healthy", pod),
                       ("smollm135m-train-plain",
                        {"host_ms_per_step.train"}),
                       ("smollm135m-train-monitored",
                        {"host_ms_per_step.train"})):
        names = {m["name"] for m in H.resolve_cell(cell, BENCH).per_layer}
        assert want <= names
