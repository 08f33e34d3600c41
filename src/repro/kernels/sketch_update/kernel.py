"""Pallas TPU kernel: batched Fail-Slow Sketch insertion.

TPU mapping of the paper's hot path (every probe record flows through
Stage-1).  The monitor's "on-chip SRAM" is split by access pattern:

* the per-record scalars live in SMEM — the streamed trace block, the
  d×m Stage-1 bucket tables (flattened to ``[d·m]``; each record probes
  ``d`` dynamic buckets with scalar loads and stores), the arrival
  counter and the drain depth ``d_n``;
* the Stage-2 pattern list and the drained-eviction rows live in VMEM as
  ``[1, L]`` / ``[1, cap]`` lane vectors.  Slot selection is a
  compare/min-reduction over the L lanes on the VPU, and every write to
  a slot is a masked vector store (``where(lane == j, new, old)``) —
  the chip has no scalar store into VMEM.

Trace records stream HBM→SMEM in blocks via the grid, and the sequential
grid preserves Algorithm 1's insertion-order semantics.  Stage-2 work
runs only for records that promote (``pl.when``), and the drain write
only on an eviction.

State tensors are passed as inputs and aliased to the outputs
(``input_output_aliases``), so the tables persist across grid steps without
ever leaving on-chip memory.

Stage-2 FIFO evictions are appended to the drained-eviction stream
(``ref.make_drain`` layout, also on-chip and aliased) before the
victim row is overwritten — the deployment's DRAM write-back of patterns
leaving on-chip SRAM, mirroring the numpy oracle's ``drained`` list.
``sketch_insert(..., drain=None)`` keeps the historical drain-less
signature (evictions discarded) and returns ``state`` only; passing a
drain buffer returns ``(state, drain)``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.sketch import HASH_A1, HASH_A2, HASH_B, SketchParams
from .. import resolve_interpret
from .ref import make_drain

# Structural contract checked by repro.analysis.kernel_audit: rank-1
# sequential grid streaming trace blocks, with the sketch state aliased
# input→output so it stays on chip across grid steps.  Algorithm
# 1 is order-sensitive — the sequential grid is load-bearing, and the
# auditor flags any dimension_semantics "parallel" annotation here.
AUDIT = {"grid_rank": 1, "aliased_io": True, "sequential_grid": True}

_I32MAX = np.int32(np.iinfo(np.int32).max)

_STATE_KEYS = ("keys_lo", "keys_hi", "valid", "freq",
               "s2_lo", "s2_hi", "s2_valid", "s2_count",
               "s2_sum", "s2_sumsq", "s2_val",
               "s2_tmin", "s2_tmax", "s2_min", "s2_arrival", "counter")

_DRAIN_KEYS = ("d_lo", "d_hi", "d_count", "d_arrival",
               "d_sum", "d_sumsq", "d_val", "d_tmin", "d_tmax", "d_min",
               "d_n")


def _hash_scalar(lo, hi, table: int, m: int):
    a1 = jnp.int32(np.uint32(HASH_A1[table] & 0xFFFFFFFF).view(np.int32))
    a2 = jnp.int32(np.uint32(HASH_A2[table] & 0xFFFFFFFF).view(np.int32))
    b = jnp.int32(np.uint32(HASH_B[table] & 0xFFFFFFFF).view(np.int32))
    x = a1 * lo + a2 * hi + b
    x = x ^ ((x >> 16) & 0xFFFF)
    x = x * jnp.int32(0x45D9F3B)
    x = x ^ ((x >> 13) & 0x7FFFF)
    x = x & jnp.int32(0x7FFFFFFF)
    return x % m


def _kernel(lo_ref, hi_ref, dur_ref, val_ref, t_ref, act_ref,
            *state_refs,
            d: int, m: int, H: int, L: int, block: int, cap: int):
    # state arrives twice (inputs, then aliased outputs); operate on the
    # output refs — aliasing makes them carry the live state.
    (klo, khi, vld, frq,
     s2lo, s2hi, s2v, s2c, s2s, s2q, s2val, s2tmin, s2tmax, s2min,
     s2arr, counter,
     dlo, dhi, dcnt, darr, dsum, dsq, dval, dtmin, dtmax, dmin,
     dnum) = state_refs[len(state_refs) // 2:]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    dlane = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)

    def body(k, _):
        lo = lo_ref[0, 0, k]
        hi = hi_ref[0, 0, k]
        dur = dur_ref[0, 0, k]
        val = val_ref[0, 0, k]
        t = t_ref[0, 0, k]
        active = act_ref[0, 0, k] == 1

        # ---- Stage-1 (scalar, SMEM) -------------------------------------
        promoted = jnp.bool_(False)
        for i in range(d):                      # unrolled: d is small
            idx = i * m + _hash_scalar(lo, hi, i, m)
            bk_lo = klo[idx]
            bk_hi = khi[idx]
            bk_v = vld[idx]
            bk_f = frq[idx]
            match = (bk_v == 1) & (bk_lo == lo) & (bk_hi == hi)
            empty = bk_v == 0
            newf = jnp.where(match, bk_f + 1,
                             jnp.where(empty, 1, bk_f - 1))
            newv = jnp.where(match | empty, 1,
                             (newf > 0).astype(jnp.int32))
            newf = jnp.where((~match) & (~empty) & (newf <= 0), 0, newf)
            klo[idx] = jnp.where(active & empty, lo, bk_lo)
            khi[idx] = jnp.where(active & empty, hi, bk_hi)
            vld[idx] = jnp.where(active, newv, bk_v)
            frq[idx] = jnp.where(active, newf, bk_f)
            promoted |= (match | empty) & (newf >= H)
        promoted &= active

        # ---- Stage-2 (vector over the L lanes, VMEM) --------------------
        @pl.when(promoted)
        def _stage2():
            v = s2v[...]
            s2_match = (v == 1) & (s2lo[...] == lo) & (s2hi[...] == hi)
            j_upd = jnp.min(jnp.where(s2_match, lane, L))
            j_free = jnp.min(jnp.where(v == 0, lane, L))
            arr = jnp.where(v == 1, s2arr[...], _I32MAX)
            j_evict = jnp.min(jnp.where(arr == jnp.min(arr), lane, L))
            exists = j_upd < L
            any_free = j_free < L
            j = jnp.where(exists, j_upd, jnp.where(any_free, j_free,
                                                   j_evict))
            sel = lane == j

            # drain the FIFO victim before its slot is overwritten (an
            # undersized buffer saturates: evictions past ``cap`` drop)
            dn = dnum[0]

            @pl.when((~exists) & (~any_free) & (dn < cap))
            def _drain():
                dsel = dlane == dn
                for dref, sref in ((dlo, s2lo), (dhi, s2hi), (dcnt, s2c),
                                   (darr, s2arr), (dsum, s2s), (dsq, s2q),
                                   (dval, s2val), (dtmin, s2tmin),
                                   (dtmax, s2tmax), (dmin, s2min)):
                    row = jnp.sum(jnp.where(sel, sref[...], 0))
                    dref[...] = jnp.where(dsel, row, dref[...])
                dnum[0] = dn + 1

            def put(ref, on_upd, on_new):
                ref[...] = jnp.where(sel, jnp.where(exists, on_upd, on_new),
                                     ref[...])

            new = sel & (~exists)
            s2lo[...] = jnp.where(new, lo, s2lo[...])
            s2hi[...] = jnp.where(new, hi, s2hi[...])
            s2arr[...] = jnp.where(new, counter[0], s2arr[...])
            s2v[...] = jnp.where(sel, 1, v)
            put(s2c, s2c[...] + 1, 1)
            put(s2s, s2s[...] + dur, dur)
            put(s2q, s2q[...] + dur * dur, dur * dur)
            put(s2val, s2val[...] + val, val)
            put(s2tmin, jnp.minimum(s2tmin[...], t), t)
            put(s2tmax, jnp.maximum(s2tmax[...], t + dur), t + dur)
            put(s2min, jnp.minimum(s2min[...], dur), dur)
            counter[0] = counter[0] + jnp.where(exists, 0, 1)
        return ()

    jax.lax.fori_loop(0, block, body, ())


@partial(jax.jit, static_argnames=("params", "block", "interpret"))
def sketch_insert(state: dict, lo, hi, dur, val, t, *,
                  params: SketchParams, block: int = 256,
                  interpret: bool | None = None, drain: dict | None = None):
    """Insert a batch of records into the sketch state via the Pallas
    kernel.  State layout matches ``ref.make_state``.  With a
    ``ref.make_drain`` buffer, Stage-2 FIFO evictions are appended to it
    and ``(state, drain)`` is returned; without one the historical
    drain-less behaviour (evictions discarded, ``state`` returned) is
    preserved."""
    want_drain = drain is not None
    if not want_drain:
        drain = make_drain(1)
    n = lo.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    act = jnp.ones((n,), jnp.int32)
    if pad:
        z32 = jnp.zeros((pad,), jnp.int32)
        zf = jnp.zeros((pad,), jnp.float32)
        lo = jnp.concatenate([lo.astype(jnp.int32), z32])
        hi = jnp.concatenate([hi.astype(jnp.int32), z32])
        dur = jnp.concatenate([dur.astype(jnp.float32), zf])
        val = jnp.concatenate([val.astype(jnp.float32), zf])
        t = jnp.concatenate([t.astype(jnp.float32), zf])
        act = jnp.concatenate([act, z32])
    else:
        lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)
        dur, val, t = (dur.astype(jnp.float32), val.astype(jnp.float32),
                       t.astype(jnp.float32))

    p = params
    cap = drain["d_lo"].shape[0]
    smem = pltpu.SMEM
    trace_spec = pl.BlockSpec((1, 1, block), lambda i: (i, 0, 0),
                              memory_space=smem)
    tbl_spec = pl.BlockSpec((p.d * p.m,), lambda i: (0,), memory_space=smem)
    one_spec = pl.BlockSpec((1,), lambda i: (0,), memory_space=smem)
    vec_spec = pl.BlockSpec((1, p.L), lambda i: (0, 0))
    drain_spec = pl.BlockSpec((1, cap), lambda i: (0, 0))
    state_specs = ([tbl_spec] * 4 + [vec_spec] * 11 + [one_spec]
                   + [drain_spec] * 10 + [one_spec])

    state_in = ([state[k].reshape(_flat_shape(k, state[k].shape))
                 for k in _STATE_KEYS]
                + [drain[k].reshape(_flat_shape(k, drain[k].shape))
                   for k in _DRAIN_KEYS])
    out_shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in state_in]
    n_trace = 6

    out = pl.pallas_call(
        partial(_kernel, d=p.d, m=p.m, H=p.H, L=p.L, block=block, cap=cap),
        grid=(nb,),
        in_specs=[trace_spec] * n_trace + state_specs,
        out_specs=state_specs,
        out_shape=out_shapes,
        input_output_aliases={n_trace + i: i
                              for i in range(len(state_in))},
        interpret=resolve_interpret(interpret),
    )(*(x.reshape(nb, 1, block) for x in (lo, hi, dur, val, t, act)),
      *state_in)
    new_state = {k: o.reshape(state[k].shape)
                 for k, o in zip(_STATE_KEYS, out)}
    if not want_drain:
        return new_state
    new_drain = {k: o.reshape(drain[k].shape)
                 for k, o in zip(_DRAIN_KEYS, out[len(_STATE_KEYS):])}
    return new_state, new_drain


def _flat_shape(key: str, shape: tuple) -> tuple:
    """Kernel-side layout of one state/drain array: scalars → ``(1,)``
    and tables → ``(d·m,)`` for SMEM, vectors → ``(1, n)`` lane rows for
    VMEM."""
    if len(shape) == 0:
        return (1,)
    if len(shape) == 2:
        return (shape[0] * shape[1],)
    return (1, shape[0])
