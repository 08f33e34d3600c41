"""The program's own spans and counters (``repro.core.spans``), as the
per-layer readers see them.

A traced run of the parent commit runs these readers too, over a program
that has no ``repro.core.spans``: there they read ``None`` and the metric
is left out of the result line, as it is when a ring dropped spans from
inside the window, rather than under-count.
"""

from __future__ import annotations

import math


def spans_in(run, name: str) -> list | None:
    """The spans called ``name`` that started inside the run's window."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.within(name, run.t0, run.t1)


def _verdicts(run) -> int | None:
    v = spans_in(run, "pod.verdict")
    return len(v) if v else None


def seconds_per_verdict(run, name: str) -> float | None:
    """Seconds of span ``name`` per ``pod.verdict`` in the window."""
    n, got = _verdicts(run), spans_in(run, name)
    if not n or got is None:
        return None
    return math.fsum(s.seconds for s in got if "verdict" in s.ids) / n


def count_per_verdict(run, name: str, counter: str) -> float | None:
    """Counter ``counter`` of span ``name`` per ``pod.verdict`` in the
    window."""
    n, got = _verdicts(run), spans_in(run, name)
    if not n or got is None:
        return None
    return math.fsum(s.counts.get(counter, 0) for s in got
                     if "verdict" in s.ids) / n


def self_seconds_per_verdict(run, name: str, child: str) -> float | None:
    """Seconds of span ``name`` less those of its ``child`` spans, per
    ``pod.verdict`` in the window."""
    n, got, kids = _verdicts(run), spans_in(run, name), spans_in(run, child)
    if not n or got is None or kids is None:
        return None
    got = [s for s in got if "verdict" in s.ids]
    seqs = {s.seq for s in got}
    return (math.fsum(s.seconds for s in got)
            - math.fsum(k.seconds for k in kids if k.parent in seqs)) / n


def ms_per_step(run, names: tuple[str, ...]) -> float | None:
    """Mean milliseconds per training step of the spans ``names`` taken
    together, over the steps that ran every one of them in the window."""
    by_step: dict[int, list[float]] = {}
    for name in names:
        got = spans_in(run, name)
        if got is None:
            return None
        for s in got:
            if "step" in s.ids:
                by_step.setdefault(s.ids["step"], []).append(s.seconds)
    full = [v for v in by_step.values() if len(v) == len(names)]
    if not full:
        return None
    return 1e3 * math.fsum(math.fsum(v) for v in full) / len(full)
