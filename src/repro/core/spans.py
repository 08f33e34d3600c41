"""Spans and counters: the program's own timings, on one clock.

An operator running SLOTH beside a job asks where a verdict's seconds or
a training step's milliseconds went.  This module answers in process and
in the profiler's trace:

* :func:`clock` is the package's one wall-clock read (``time.perf_counter``).
  It feeds timings only — never a verdict, an outcome or an RNG stream —
  so the determinism lint (``repro.analysis.lints`` rule ``wallclock``)
  has exactly one allowlisted reader to audit.
* :func:`span` times a block of work.  Each closed span records its name,
  start and end on :func:`clock`, the enclosing span (a ``contextvars``
  stack, so threads and tasks keep their own), the ids of the roots it
  runs under (``verdict=<n>``, ``step=<n>``, inherited by every child),
  its attributes and its counters.  It also enters
  ``jax.profiler.TraceAnnotation(name)``: when a profiler trace runs,
  every span lands on the trace's host plane, on the device events'
  clock, so a device idle gap can be put down to the span that covers it.
* :func:`count` adds to the innermost open span's counters.  Work sizes
  (runs scanned, patterns decoded, path events solved, FailRank
  iterations) are counted at span boundaries; no span is opened inside a
  per-item loop.
* Closed spans go to an in-memory ring per name, ``RING_SIZE`` entries
  each; a ring that overflows drops its oldest entries and counts them.
  :func:`within` returns ``None`` rather than a short list when entries
  it would have returned were dropped.

An operator reads both in process: ``within("sloth.detect.links.em", t0,
t1)`` lists the EM's spans started in ``[t0, t1]`` on :func:`clock`, each
with ``.seconds``; ``within("sloth.detect.links")`` gives the same for link
detection, and each span's ``.counts["events"]`` is the number of path
events its EM solved.  Spans of one verdict share ``ids["verdict"]``.

Recording is always on and costs a few microseconds per span; a span adds
no device synchronisation.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import functools
import itertools
import math
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["RING_SIZE", "Span", "clock", "count", "dropped", "span",
           "traced", "within"]

#: Entries kept per span name.
RING_SIZE = 4096


def clock() -> float:
    """The package's one blessed wall-clock read, in seconds
    (``time.perf_counter``).  It feeds timings only — spans, the launcher's
    step timing, reported wall times — never a verdict, a scenario
    outcome or an RNG stream.  Centralised so the determinism lint
    (``repro.analysis.lints`` rule ``wallclock``) has exactly one
    allowlisted reader to audit."""
    return time.perf_counter()  # lint: allow-wallclock


@dataclasses.dataclass(slots=True, eq=False)
class Span:
    """One timed block of work; ``t1`` is NaN while it is open."""
    name: str
    seq: int                    # unique, in order of opening
    parent: int | None          # ``seq`` of the enclosing span
    ids: dict[str, int]         # root ids, e.g. {"step": 12, "verdict": 1}
    attrs: dict
    t0: float
    t1: float = math.nan
    counts: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Ring:
    __slots__ = ("items", "dropped", "last_dropped_t0")

    def __init__(self, size: int):
        self.items: collections.deque[Span] = collections.deque(maxlen=size)
        self.dropped = 0
        self.last_dropped_t0 = -math.inf


_current: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "repro_span", default=None)
_seq = itertools.count()
_root_ids: dict[str, itertools.count] = {}
_rings: dict[str, _Ring] = {}
_lock = threading.Lock()


class _Open:
    """The context manager :func:`span` returns; yields the :class:`Span`."""
    __slots__ = ("_name", "_root", "_attrs", "_span", "_token", "_ann")

    def __init__(self, name: str, root: str | None, attrs: dict):
        self._name, self._root, self._attrs = name, root, attrs

    def __enter__(self) -> Span:
        parent = _current.get()
        ids = parent.ids if parent is not None else {}
        if self._root is not None:
            ids = {**ids, self._root: next(_root_ids.setdefault(
                self._root, itertools.count()))}
        s = Span(self._name, next(_seq),
                 parent.seq if parent is not None else None, ids,
                 self._attrs, math.nan)
        self._span = s
        self._token = _current.set(s)
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        s.t0 = clock()
        return s

    def __exit__(self, *exc) -> bool:
        s = self._span
        s.t1 = clock()
        self._ann.__exit__(*exc)
        _current.reset(self._token)
        with _lock:
            ring = _rings.get(s.name)
            if ring is None:
                ring = _rings[s.name] = _Ring(RING_SIZE)
            if len(ring.items) == ring.items.maxlen:
                ring.dropped += 1
                ring.last_dropped_t0 = max(ring.last_dropped_t0,
                                           ring.items[0].t0)
            ring.items.append(s)
        return False


def span(name: str, root: str | None = None, **attrs) -> _Open:
    """Time the ``with`` block as span ``name``.

    ``root`` opens a new id under that key (``root="verdict"`` gives
    ``verdict=0``, ``1``, ... in order), which every span inside inherits
    beside the ids of the spans around it.  ``attrs`` are kept as given.
    """
    return _Open(name, root, attrs)


def traced(name: str, root: str | None = None):
    """Decorator: every call of the function runs inside
    ``span(name, root)``; the function keeps its name."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            with _Open(name, root, {}):
                return fn(*args, **kw)
        return call
    return wrap


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span; a count
    made outside every span is dropped."""
    cur = _current.get()
    if cur is not None:
        cur.counts[name] = cur.counts.get(name, 0) + n


def within(name: str, t0: float = -math.inf,
           t1: float = math.inf) -> list[Span] | None:
    """Closed spans called ``name`` that started inside ``[t0, t1]``,
    in the order they closed; ``None`` when the ring has dropped a span
    that started at or after ``t0``, since the list could then be short."""
    with _lock:
        ring = _rings.get(name)
        if ring is None:
            return []
        if ring.dropped and ring.last_dropped_t0 >= t0:
            return None
        return [s for s in ring.items if t0 <= s.t0 <= t1]


def dropped(name: str) -> int:
    """Spans called ``name`` that the ring has dropped so far."""
    ring = _rings.get(name)
    return ring.dropped if ring is not None else 0

