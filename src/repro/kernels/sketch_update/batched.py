"""Vectorized multi-record Fail-Slow Sketch insertion (campaign hot path).

Same Algorithm-1 semantics as ``ref.insert_batch`` (and therefore the
``core/sketch.py`` numpy oracle), restructured for batch throughput:

* bucket indices for **all** records × all ``d`` hash tables are computed
  up front in one vectorized ``hash_all`` call (the per-record path
  re-hashes inside every step),
* Stage-1 state is packed ``[d, m, 4]`` (lo, hi, valid, freq) and the
  per-table update rule is ``vmap``-ed over the ``d`` hash tables, so one
  record costs one batched gather + one batched scatter,
* Stage-2 state is packed into an int ``[L, 5]`` (lo, hi, valid, count,
  arrival) and a float ``[L, 6]`` (sum, sumsq, val, tmin, tmax, min)
  matrix — one row scatter each per record instead of eleven vector
  scatters,
* records are applied in order by ``lax.scan`` (insertion order is
  semantically load-bearing: Stage-1 frequencies race between keys sharing
  a bucket and Stage-2 eviction is FIFO by promotion arrival).

Two extensions over the original per-record scan:

* **Drained-eviction stream** (``insert_batch_drained`` /
  ``insert_runs_vectorized`` + ``ref.make_drain``): a Stage-2 FIFO
  eviction appends the victim row to the drain buffer before the slot is
  overwritten — the numpy oracle keeps those patterns in ``self.drained``
  and merges them back in ``patterns()``, so losing them was a
  correctness divergence under eviction pressure.
* **Run-compressed insertion** (``insert_runs_vectorized``): the
  vectorized analogue of ``FailSlowSketch.insert_run`` — one scan step
  applies a whole run of ``r`` identical-key records (Stage-1 frequencies
  move by ±r with the exact promote/steal index algebra of the oracle;
  Stage-2 receives the closed-form aggregates), so instruction expansion
  never materialises per-record arrays.

The packing is an internal layout change only: inputs/outputs use the
``ref.make_state`` / ``ref.make_drain`` dict layouts, integer state is
bit-identical to the sequential reference and the float statistics
accumulate in the same float32 order.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .ref import hash_all, make_drain

_I32MAX = jnp.iinfo(jnp.int32).max

_S1_COLS = (("keys_lo", 0), ("keys_hi", 1), ("valid", 2), ("freq", 3))
_S2I_COLS = (("s2_lo", 0), ("s2_hi", 1), ("s2_valid", 2), ("s2_count", 3),
             ("s2_arrival", 4))
_S2F_COLS = (("s2_sum", 0), ("s2_sumsq", 1), ("s2_val", 2), ("s2_tmin", 3),
             ("s2_tmax", 4), ("s2_min", 5))
_DI_COLS = (("d_lo", 0), ("d_hi", 1), ("d_count", 2), ("d_arrival", 3))
_DF_COLS = (("d_sum", 0), ("d_sumsq", 1), ("d_val", 2), ("d_tmin", 3),
            ("d_tmax", 4), ("d_min", 5))


def _pack(state, drain):
    T = jnp.stack([state[k] for k, _ in _S1_COLS], axis=2)
    I = jnp.stack([state[k] for k, _ in _S2I_COLS], axis=1)
    F = jnp.stack([state[k] for k, _ in _S2F_COLS], axis=1)
    DI = jnp.stack([drain[k] for k, _ in _DI_COLS], axis=1)
    DF = jnp.stack([drain[k] for k, _ in _DF_COLS], axis=1)
    return T, I, F, state["counter"], DI, DF, drain["d_n"]


def _unpack(state, drain, carry):
    T, I, F, C, DI, DF, Dn = carry
    out = dict(state, counter=C)
    for k, col in _S1_COLS:
        out[k] = T[..., col]
    for k, col in _S2I_COLS:
        out[k] = I[:, col]
    for k, col in _S2F_COLS:
        out[k] = F[:, col]
    dout = dict(drain, d_n=Dn)
    for k, col in _DI_COLS:
        dout[k] = DI[:, col]
    for k, col in _DF_COLS:
        dout[k] = DF[:, col]
    return out, dout


def _one_table(tbl, j, lo, hi, H):
    """Stage-1 update for one packed hash-table row ``[m, 4]`` (vmapped
    over the d tables); returns (new row, promoted-on-this-table)."""
    bk = tbl[j]                                  # (lo, hi, valid, freq)
    match = (bk[2] == 1) & (bk[0] == lo) & (bk[1] == hi)
    empty = bk[2] == 0
    newf = jnp.where(match, bk[3] + 1, jnp.where(empty, 1, bk[3] - 1))
    newv = jnp.where(match | empty, 1, (newf > 0).astype(jnp.int32))
    newlo = jnp.where(empty, lo, bk[0])
    newhi = jnp.where(empty, hi, bk[1])
    newf = jnp.where((~match) & (~empty) & (newf <= 0), 0, newf)
    promoted = (match | empty) & (newf >= H)
    return tbl.at[j].set(jnp.stack([newlo, newhi, newv, newf])), promoted


def _one_table_run(tbl, j, lo, hi, r, active, H):
    """Stage-1 update for a run of ``r`` identical-key records on one
    packed table row; returns (new row, 0-based index of this table's
    first promoted record — ``r`` if the run never promotes here).

    Mirrors ``FailSlowSketch.insert_run`` exactly: a matching bucket with
    prior freq ``f0`` promotes record ``k = H − f0 − 1``; an empty bucket
    promotes ``k = H − 1``; a contested bucket absorbs ``r ≤ f0``
    decrements without promotion, while ``r > f0`` clears it (record
    ``f0`` steals the bucket) and promotes ``k = f0 + H − 1``.
    """
    bk = tbl[j]
    match = (bk[2] == 1) & (bk[0] == lo) & (bk[1] == hi)
    empty = bk[2] == 0
    f0 = bk[3]
    steal = (~match) & (~empty) & (r > f0)
    newf = jnp.where(match, f0 + r,
                     jnp.where(empty, r,
                               jnp.where(steal, r - f0, f0 - r)))
    claim = empty | steal
    newv = jnp.where(match | claim, 1, (newf > 0).astype(jnp.int32))
    newlo = jnp.where(claim, lo, bk[0])
    newhi = jnp.where(claim, hi, bk[1])
    k = jnp.where(match, H - f0 - 1,
                  jnp.where(empty, H - 1,
                            jnp.where(steal, f0 + H - 1, r)))
    row = jnp.where(active, jnp.stack([newlo, newhi, newv, newf]), bk)
    return tbl.at[j].set(row), jnp.where(active, jnp.maximum(k, 0), r)


_tables = jax.vmap(_one_table, in_axes=(0, 0, None, None, None))
_tables_run = jax.vmap(_one_table_run,
                       in_axes=(0, 0, None, None, None, None, None))


def _stage2(I, F, C, DI, DF, Dn, lo, hi, promoted,
            n, sdur, ssq, sval, tfirst, tlast, mdur):
    """Stage-2 slot selection + update for one promotion event carrying
    pre-aggregated statistics (n records; per-record steps pass n = 1).
    FIFO evictions are appended to the (DI, DF, Dn) drain stream before
    the victim row is overwritten."""
    valid = I[:, 2]
    s2_match = (valid == 1) & (I[:, 0] == lo) & (I[:, 1] == hi)
    exists = jnp.any(s2_match)
    j_upd = jnp.argmax(s2_match)
    free = valid == 0
    any_free = jnp.any(free)
    j_free = jnp.argmax(free)
    j_evict = jnp.argmin(jnp.where(valid == 1, I[:, 4], _I32MAX))
    j = jnp.where(exists, j_upd, jnp.where(any_free, j_free, j_evict))

    ri, rf = I[j], F[j]
    # drain the FIFO victim (valid row, no free slot, new key arriving);
    # the buffer write is index-clamped so an undersized buffer saturates
    # instead of scattering out of bounds
    evict = promoted & ~exists & ~any_free
    slot = jnp.minimum(Dn, DI.shape[0] - 1)
    keep = evict & (Dn < DI.shape[0])
    DI = DI.at[slot].set(jnp.where(
        keep, jnp.stack([ri[0], ri[1], ri[3], ri[4]]), DI[slot]))
    DF = DF.at[slot].set(jnp.where(keep, rf, DF[slot]))
    Dn = Dn + keep.astype(jnp.int32)

    upd_i = jnp.stack([ri[0], ri[1], 1, ri[3] + n, ri[4]])
    new_i = jnp.stack([lo, hi, 1, n, C])
    upd_f = jnp.stack([rf[0] + sdur, rf[1] + ssq, rf[2] + sval,
                       jnp.minimum(rf[3], tfirst),
                       jnp.maximum(rf[4], tlast),
                       jnp.minimum(rf[5], mdur)])
    new_f = jnp.stack([sdur, ssq, sval, tfirst, tlast, mdur])
    I = I.at[j].set(jnp.where(promoted,
                              jnp.where(exists, upd_i, new_i), ri))
    F = F.at[j].set(jnp.where(promoted,
                              jnp.where(exists, upd_f, new_f), rf))
    C = C + jnp.where(promoted & ~exists, 1, 0).astype(jnp.int32)
    return I, F, C, DI, DF, Dn


def _step(carry, xs, H: int):
    """One per-record scan step (Algorithm 1, record granularity)."""
    T, I, F, C, DI, DF, Dn = carry
    idx, lo, hi, dur, val, t = xs
    T, prom = _tables(T, idx, lo, hi, H)
    promoted = jnp.any(prom)
    I, F, C, DI, DF, Dn = _stage2(
        I, F, C, DI, DF, Dn, lo, hi, promoted,
        jnp.int32(1), dur, dur * dur, val, t, t + dur, dur)
    return (T, I, F, C, DI, DF, Dn), None


def _step_run(carry, xs, H: int):
    """One run-compressed scan step: ``r`` records of one key, starting at
    ``t0`` with stride ``dt``, each lasting ``dur``.  The first promoted
    record index is the minimum over tables (``FailSlowSketch
    .insert_run``); records ``first..r-1`` reach Stage-2 as closed-form
    aggregates."""
    T, I, F, C, DI, DF, Dn = carry
    idx, lo, hi, r, dur, val, t0, dt = xs
    active = r > 0
    T, ks = _tables_run(T, idx, lo, hi, r, active, H)
    first = jnp.minimum(jnp.min(ks), r)
    promoted = active & (first < r)
    n = r - first
    nf = n.astype(jnp.float32)
    tfirst = t0 + dt * first.astype(jnp.float32)
    tlast = t0 + dt * jnp.maximum(r - 1, 0).astype(jnp.float32) + dur
    I, F, C, DI, DF, Dn = _stage2(
        I, F, C, DI, DF, Dn, lo, hi, promoted,
        n, nf * dur, nf * dur * dur, nf * val, tfirst, tlast, dur)
    return (T, I, F, C, DI, DF, Dn), None


def _cast_records(lo, hi, dur, val, t):
    return (lo.astype(jnp.int32), hi.astype(jnp.int32),
            dur.astype(jnp.float32), val.astype(jnp.float32),
            t.astype(jnp.float32))


@partial(jax.jit, static_argnames=("H",))
def insert_batch_drained(state, drain, lo, hi, dur, val, t, *, H: int):
    """Insert a whole record batch, draining Stage-2 FIFO evictions.

    Equivalent to ``ref.insert_batch`` / per-record ``FailSlowSketch
    .insert`` calls in order, with hashing hoisted out of the sequential
    loop, the table update vectorized over ``d`` and the state packed so
    each record costs a handful of row scatters.  ``drain`` is a
    ``ref.make_drain`` buffer (size it to the batch length — one record
    evicts at most one row); returns ``(state, drain)``.
    """
    d, m = state["keys_lo"].shape
    lo, hi, dur, val, t = _cast_records(lo, hi, dur, val, t)
    idx_all = hash_all(lo, hi, d, m)             # [n, d], one shot
    carry, _ = jax.lax.scan(partial(_step, H=H), _pack(state, drain),
                            (idx_all, lo, hi, dur, val, t))
    return _unpack(state, drain, carry)


def insert_batch_vectorized(state, lo, hi, dur, val, t, *, H: int):
    """Drain-less compatibility wrapper around ``insert_batch_drained``:
    state transitions are identical (the Stage-2 tables never depended on
    the drain buffer); FIFO-evicted rows are simply discarded, as the
    original scan did.  The throwaway buffer is capacity-1 — the
    saturation clamp absorbs every eviction at O(1) carry instead of
    threading an O(n) buffer through the scan."""
    state, _ = insert_batch_drained(state, make_drain(1),
                                    lo, hi, dur, val, t, H=H)
    return state


@partial(jax.jit, static_argnames=("H",))
def insert_runs_vectorized(state, drain, lo, hi, reps, dur, val, t0, dt,
                           *, H: int):
    """Insert run-length-compressed records: run ``i`` is ``reps[i]``
    consecutive records of key ``(lo[i], hi[i])``, record ``k`` starting
    at ``t0[i] + k·dt[i]`` and lasting ``dur[i]`` with value ``val[i]``.

    The vectorized analogue of ``FailSlowSketch.insert_run`` — bit-exact
    Stage-1 tables and promotion indices, Stage-2 fed the same closed-form
    aggregates (float32 here) — so instruction expansion never
    materialises per-record arrays.  Runs with ``reps ≤ 0`` are no-ops.
    Returns ``(state, drain)``; a run evicts at most one Stage-2 row, so
    ``make_drain(len(runs))`` can never saturate.
    """
    d, m = state["keys_lo"].shape
    lo, hi, dur, val, t0 = _cast_records(lo, hi, dur, val, t0)
    reps = reps.astype(jnp.int32)
    dt = dt.astype(jnp.float32)
    idx_all = hash_all(lo, hi, d, m)             # [n, d], one shot
    # the scope names the scan's device op in a profiler trace
    with jax.named_scope("sketch.scan"):
        carry, _ = jax.lax.scan(partial(_step_run, H=H), _pack(state, drain),
                                (idx_all, lo, hi, reps, dur, val, t0, dt))
    return _unpack(state, drain, carry)
