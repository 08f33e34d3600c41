"""The trace reduction (bench/devtrace.py) on intervals with known
answers, and on a profiler trace recorded on the CPU."""

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace as D  # noqa: E402


def test_merge_and_union_of_overlapping_intervals():
    iv = [(5.0, 6.0), (0.0, 1.0), (0.5, 2.0), (2.0, 3.0), (4.0, 4.0)]
    assert D.merge(iv) == [(0.0, 3.0), (5.0, 6.0)]
    assert D.union_length(iv) == pytest.approx(4.0)


def test_gaps_are_the_uncovered_parts_of_the_window():
    busy = [(1.0, 2.0), (1.5, 3.0), (6.0, 12.0), (-1.0, 0.5)]
    assert D.gaps(busy, 0.0, 10.0) == [(0.5, 1.0), (3.0, 6.0)]
    assert D.gaps([], 0.0, 2.0) == [(0.0, 2.0)]


def test_gaps_go_to_the_innermost_host_span():
    spans = [("outer", 0.0, 10.0), ("inner", 3.0, 5.0)]
    got = D.attribute_gaps([(3.5, 4.5), (6.0, 8.0), (11.0, 12.0)], spans)
    assert got == pytest.approx({"inner": 1.0, "outer": 2.0, "host": 1.0})


def test_summary_of_a_synthetic_trace():
    ops = [[("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0),
            ("%dot = f32[8] dot(...)", 1.5, 3.0),
            ("%fusion.1 = f32[8] fusion(...)", 8.0, 9.0),
            ("copy", 9.5, 11.0)]]
    spans = [("detect", 3.0, 8.0), ("window", 0.0, 10.0)]
    s = D.summarize(ops, spans, (0.0, 10.0))
    assert s["window_s"] == pytest.approx(10.0)
    assert s["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert s["idle_share"] == pytest.approx(1 - 3.5 / 10)
    assert s["device_ops"][0] == ["%fusion.1", pytest.approx(2.0)]
    assert dict(s["device_ops"])["copy"] == pytest.approx(0.5)
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"detect": 5.0, "window": 1.0 + 0.5})


def test_a_trace_that_stops_part_way_counts_only_what_it_covers():
    """The profiler dropped every device event after 4 s of a 10 s window:
    the time past the last kept operation is not counted as idle."""
    ops = [[("scan", 0.5, 1.5), ("scan", 2.5, 3.5), ("slice", 3.5, 4.0)]]
    spans = [("record", 0.0, 1.5), ("detect", 1.5, 2.5),
             ("record", 2.5, 4.0), ("detect", 4.0, 6.0),
             ("record", 6.0, 10.0)]
    s = D.summarize(ops, spans, (0.0, 10.0))
    assert s["window_s"] == pytest.approx(4.0)
    assert s["busy_s"] == pytest.approx(2.5)
    assert s["idle_share"] == pytest.approx(1 - 2.5 / 4.0)
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"record": 0.5, "detect": 1.0})
    # no operation in the window: the whole window, all idle
    s = D.summarize([[("scan", 11.0, 12.0)]], [], (0.0, 10.0))
    assert s["window_s"] == pytest.approx(10.0)
    assert s["idle_share"] == pytest.approx(1.0)


def test_busy_is_averaged_over_devices():
    ops = [[("a", 0.0, 4.0)], [("a", 0.0, 2.0)]]
    s = D.summarize(ops, [], (0.0, 4.0))
    assert s["busy_s"] == pytest.approx(3.0)
    assert s["n_devices"] == 2


def test_a_recorded_trace_gives_its_window_and_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.detect"):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    device_ops, spans, window = D.read_profile(D.newest_xplane(tmp_path))
    assert window is not None and window[1] - window[0] >= 0.02
    names = [n for n, _, _ in spans]
    assert names == ["detect"]
    _, s, e = spans[0]
    assert window[0] <= s <= e <= window[1]
    # the CPU has no TPU plane: the reduction refuses rather than guess
    assert device_ops == []
    with pytest.raises(ValueError):
        D.reduce_profile(tmp_path)
    assert not tmp_path.joinpath("plugins").exists()
