"""Jitted public API for the sketch-update kernel.

``insert(state, traces, impl=...)`` dispatches between the Pallas kernel
(compiled on a TPU, interpreted on the CPU; see ``repro.kernels``), the
vectorized multi-record batch path (``impl="batched"``, the campaign hot
path) and the pure-jnp scan oracle.  ``insert_runs`` is the run-compressed entry point (the
vectorized analogue of ``FailSlowSketch.insert_run``; the recorder's
on-device path).  ``patterns(state)`` decodes Stage-2 into the same
Pattern records the numpy reference produces; given a drained-eviction
buffer it merges drained partials with the live list exactly like
``FailSlowSketch.patterns(include_drained=True)``.
"""

from __future__ import annotations

import numpy as np

from ...core.sketch import Pattern, SketchParams, accumulate_pattern
from . import batched as V
from . import kernel as K
from . import ref as R


def make_state(params: SketchParams):
    return R.make_state(params)


def make_drain(capacity: int):
    """Drained-eviction buffer for up to ``capacity`` Stage-2 evictions
    (one insert call over n records/runs evicts at most n rows)."""
    return R.make_drain(capacity)


def insert(state, lo, hi, dur, val, t, *, params: SketchParams,
           impl: str = "pallas", interpret: bool | None = None,
           block: int = 256,
           drain=None):
    """Per-record batched insertion.  With ``drain`` (a ``make_drain``
    buffer), FIFO-evicted Stage-2 rows are preserved and ``(state,
    drain)`` is returned; without it evictions are discarded and only
    ``state`` returns (the historical contract, still bit-identical on
    state).  The pure-jnp scan oracle (``impl="ref"``) has no drain
    support — it exists to pin the kernels' state transitions."""
    if impl == "pallas":
        return K.sketch_insert(state, lo, hi, dur, val, t, params=params,
                               block=block, interpret=interpret,
                               drain=drain)
    if impl == "batched":
        if drain is None:
            return V.insert_batch_vectorized(state, lo, hi, dur, val, t,
                                             H=params.H)
        return V.insert_batch_drained(state, drain, lo, hi, dur, val, t,
                                      H=params.H)
    if drain is not None:
        raise ValueError("impl='ref' does not support a drain buffer")
    return R.insert_batch(state, lo, hi, dur, val, t, H=params.H)


def insert_runs(state, drain, lo, hi, reps, dur, val, t0, dt, *,
                params: SketchParams):
    """Run-compressed insertion: run ``i`` stands for ``reps[i]``
    consecutive records of key ``(lo[i], hi[i])`` starting at ``t0[i]``
    with stride ``dt[i]``.  Returns ``(state, drain)``."""
    return V.insert_runs_vectorized(state, drain, lo, hi, reps, dur, val,
                                    t0, dt, H=params.H)


_PAT_COLS = ("count", "sum", "sumsq", "val", "tmin", "tmax", "arrival",
             "min")


def _bulk_rows(merged: dict[int, Pattern], arr, pre: str, idx,
               key_tag: int):
    """Decode rows ``idx`` of a state/drain dict (field prefix ``pre``)
    into Patterns, accumulating into ``merged``.  Bulk device→host
    transfer + ``tolist`` — per-row scalar reads on device arrays would
    each sync."""
    if len(idx) == 0:
        return
    keys = (np.asarray(arr[pre + "lo"])[idx].astype(np.int64)
            + (np.asarray(arr[pre + "hi"])[idx].astype(np.int64) << 31))
    cols = [keys.tolist()] + [np.asarray(arr[pre + c])[idx].tolist()
                              for c in _PAT_COLS]
    for key, cnt, s, sq, v, tmin, tmax, arrival, mind in zip(*cols):
        accumulate_pattern(merged, Pattern(key | key_tag, cnt, s, sq, v,
                                           tmin, tmax, arrival, mind))


def drain_patterns(drain, key_tag: int = 0) -> list[Pattern]:
    """Decode ONLY a drained-eviction buffer into (partial) Patterns,
    merged per key, in eviction order.  The streaming recorder uses this
    to fold each ``insert_runs`` call's evictions into a host-side
    accumulator and then reuse a fresh drain buffer for the next chunk —
    packed sketch state stays on device across ``observe()`` calls while
    the drained stream grows off-chip, exactly like the deployment's
    DRAM write stream."""
    merged: dict[int, Pattern] = {}
    _bulk_rows(merged, drain, "d_", np.arange(int(np.asarray(
        drain["d_n"]))), key_tag)
    return sorted(merged.values(), key=lambda p: p.arrival)


def patterns(state, drain=None, key_tag: int = 0) -> list[Pattern]:
    """Decode Stage-2 (and, when given, the drained-eviction stream) into
    Pattern records, merged per key exactly like the numpy oracle's
    ``patterns(include_drained=True)`` (a drained key that re-promotes
    later appears as two partials; they merge here).

    ``key_tag`` is OR-ed into every reconstructed key: the sketch's
    (lo, hi) halves preserve key bits 0–61, so a key space tagged above
    bit 61 (the comp space, ``probes.COMP_KEY_TAG``) must have its tag
    restored by the caller, who knows which sketch it is reading.
    """
    merged: dict[int, Pattern] = {}
    if drain is not None:
        _bulk_rows(merged, drain, "d_", np.arange(int(np.asarray(
            drain["d_n"]))), key_tag)
    _bulk_rows(merged, state, "s2_",
               np.nonzero(np.asarray(state["s2_valid"]))[0], key_tag)
    return sorted(merged.values(), key=lambda p: p.arrival)
