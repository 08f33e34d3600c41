"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; an operation's interval is an event on the plane's
``XLA Ops`` line.  The window is the host annotation ``bench.window``
that the harness puts around the measured window; host spans
``bench.<name>`` say what the host was doing in each idle gap.

* covered window: from the window's opening to the end of the last
  operation that any device plane kept inside it.  The profiler keeps a
  bounded number of device events and drops the rest, so a long window's
  trace can stop part-way; past its last kept operation it says nothing,
  and counting that time as idle would over-read the idle share.  Where
  nothing was dropped, this leaves out only the host's tail after the
  window's last device operation;
* busy: the union of the operation intervals inside the covered window,
  averaged over the device planes;
* idle share: 1 - busy / covered window;
* device_ops: seconds per operation name, largest first;
* idle_gaps: the device's idle seconds inside the covered window, by the
  innermost host span that covers each gap's middle (``host`` where none
  does), largest first.
"""

from __future__ import annotations

import glob
import math
import os
import shutil
from collections import defaultdict

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union_length(intervals) -> float:
    return math.fsum(e - s for s, e in merge(intervals))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute_gaps(gap_list, host_spans) -> dict[str, float]:
    """Seconds of idle gap by the innermost (shortest) host span that
    covers each gap's middle; ``host`` where none does.  Spans are
    ``(name, start, end)``."""
    import numpy as np
    if not gap_list:
        return {}
    g = np.asarray(gap_list, dtype=np.float64)
    order = np.argsort((g[:, 0] + g[:, 1]) / 2)
    g = g[order]
    mid = (g[:, 0] + g[:, 1]) / 2
    best = np.full(len(g), -1)
    best_len = np.full(len(g), np.inf)
    for i, (_, hs, he) in enumerate(host_spans):
        sel = slice(np.searchsorted(mid, hs, side="left"),
                    np.searchsorted(mid, he, side="right"))
        inner = (he - hs) < best_len[sel]
        best[sel] = np.where(inner, i, best[sel])
        best_len[sel] = np.where(inner, he - hs, best_len[sel])
    out: dict[str, float] = defaultdict(float)
    secs = g[:, 1] - g[:, 0]
    for i in np.unique(best):
        name = "host" if i < 0 else host_spans[i][0]
        out[name] += float(np.sum(secs[best == i]))
    return dict(out)


def op_name(hlo: str) -> str:
    """An operation's short name: the HLO text up to `` = ``."""
    return hlo.split(" = ", 1)[0]


def summarize(device_ops: list[list[tuple[str, float, float]]],
              host_spans: list[tuple[str, float, float]],
              window: tuple[float, float]) -> dict:
    """The numbers of one trace, from plain intervals (seconds).

    ``device_ops`` holds one list per device of ``(name, start, end)``;
    ``host_spans`` the host's ``(name, start, end)``; ``window`` the
    traced window's bounds.  ``window_s`` is the covered window's length.
    """
    lo, hi = window
    hi = max((min(e, hi) for ops in device_ops for _, s, e in ops
              if e > lo and s < hi), default=hi)
    window_s = hi - lo
    busy_per_dev, by_op, idle = [], defaultdict(float), defaultdict(float)
    for ops in device_ops:
        iv = clip([(s, e) for _, s, e in ops], lo, hi)
        busy_per_dev.append(union_length(iv))
        for name, s, e in ops:
            if e > lo and s < hi:
                by_op[op_name(name)] += min(e, hi) - max(s, lo)
        spans = [h for h in host_spans if h[0] != WINDOW_SPAN]
        for name, sec in attribute_gaps(gaps(iv, lo, hi), spans).items():
            idle[name] += sec / len(device_ops)
    busy = math.fsum(busy_per_dev) / max(len(busy_per_dev), 1)
    return {
        "busy_s": busy,
        "window_s": window_s,
        "idle_share": 1.0 - busy / window_s if window_s > 0 else None,
        "n_devices": len(device_ops),
        "device_ops": sorted(([k, v] for k, v in by_op.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
    }


def newest_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_profile(path: str):
    """``(device_ops, host_spans, window)`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, host_spans, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((ev.name, s, s + ev.duration_ns * 1e-9))
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
                    else:
                        host_spans.append((ev.name[len("bench."):], s, e))
    return device_ops, host_spans, window


def reduce_profile(trace_dir, keep: bool = False) -> dict:
    """The summary of the newest trace under ``trace_dir``, which is then
    deleted unless ``keep``: a trace of a long window is large."""
    path = newest_xplane(trace_dir)
    device_ops, host_spans, window = read_profile(path)
    if not keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    if not device_ops:
        raise ValueError("the trace holds no TPU device plane")
    return summarize(device_ops, host_spans, window)
