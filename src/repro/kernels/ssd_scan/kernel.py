"""Pallas TPU kernel: chunked Mamba-2 SSD scan.

Grid (BH, n_chunks): the chunk dimension is sequential; the inter-chunk
state S [P, N] lives in VMEM scratch across chunk steps (TPU revisiting
semantics).  Per chunk the kernel computes the intra-chunk dual form (an
MXU [Q,Q]·[Q,P] product with the decay-masked score matrix), adds the
inter-chunk contribution C·Sᵀ·exp(cum), and updates the carried state —
the same decomposition as ``repro.models.mamba2.ssd_chunked``, tiled so
chunk Q and head dim P align to the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

# Structural contract checked by repro.analysis.kernel_audit: rank-2
# grid (bh, chunks); the inter-chunk state lives in VMEM scratch (no
# aliasing), carried by the sequential chunk axis.
AUDIT = {"grid_rank": 2, "aliased_io": False, "sequential_grid": True}


def _diag_to_col(row, eye):
    """[1, Q] row → [Q, 1] column without a transpose: broadcast the row
    down the sublanes, keep the diagonal, reduce along the lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _diag_to_row(col, eye):
    """[Q, 1] column → [1, Q] row (the inverse of ``_diag_to_col``)."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, out_state_ref,
            state_scr, *, chunk: int, seq_len: int):
    h = pl.program_id(0)
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[:] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # [Q, P]
    dt_row = dt_ref[0].astype(jnp.float32)    # [1, Q] (lane-major block)
    a = a_ref[h]                              # scalar (SMEM)
    b = b_ref[0].astype(jnp.float32)          # [Q, N]
    c = c_ref[0].astype(jnp.float32)          # [Q, N]

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = qi == kj

    # sequence mask for the padded tail
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    pos = ci * chunk + row
    live = (pos < seq_len).astype(jnp.float32)                # [Q, 1]
    dt = _diag_to_col(dt_row, eye) * live                     # [Q, 1]

    # log-decay prefix sums as masked reductions: Pallas TPU has no cumsum
    l_row = _diag_to_row(dt * a, eye)                         # [1, Q]
    cum = jnp.sum(jnp.where(kj <= qi, l_row, 0.0), axis=1,
                  keepdims=True)                              # [Q, 1]
    cum_row = _diag_to_row(cum, eye)                          # [1, Q]
    cum_end = jnp.sum(jnp.where(row == chunk - 1, cum, 0.0))

    # intra-chunk dual form
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.exp(jnp.minimum(cum - cum_row, 0.0))
    gmat = jnp.where(kj <= qi, scores * decay, 0.0)
    xdt = x * dt
    y = jax.lax.dot_general(gmat, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    state = state_scr[:]                      # [P, N]
    y += jnp.exp(cum) * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S ← exp(cum_end)·S + Σ_j exp(cum_end−cum_j)·dt_j·x_j⊗B_j
    w_end = jnp.exp(cum_end - cum) * dt       # [Q, 1]
    upd = jax.lax.dot_general(x * w_end, b,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_scr[:] = jnp.exp(cum_end) * state + upd

    y_ref[0] = (y * live).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        out_state_ref[0] = state_scr[:]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 128,
             interpret: bool | None = None):
    """x [BH,S,P], dt [BH,S], a [BH], b/c [BH,S,N] →
    (y [BH,S,P], final_state [BH,P,N]).

    ``dt`` enters the kernel as ``[BH, 1, S]`` with ``(1, 1, chunk)``
    blocks, so its last two block dims satisfy the TPU tiling rule (the
    full unit dim, then a lane-multiple chunk); ``a`` sits whole in SMEM
    and each grid row reads its own scalar."""
    bh, s, p = x.shape
    n = b.shape[-1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))

    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, seq_len=s),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, chunk), lambda h, i: (h, 0, i)),
            pl.BlockSpec((bh,), lambda h, i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, p, n), lambda h, i: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc * chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt.reshape(bh, 1, nc * chunk), a.astype(jnp.float32), b, c)
    return y[:, :s], state
