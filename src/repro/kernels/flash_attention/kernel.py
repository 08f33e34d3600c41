"""Pallas TPU flash attention (blocked online softmax).

Grid: (batch×kv-heads×q-groups, q_blocks, kv_blocks); the kv dimension is
sequential ("arbitrary") so the running max / denominator / accumulator
live in VMEM scratch across kv steps.  Causal and sliding-window masking
are applied per block pair; unlike the XLA fallback, fully-masked kv blocks
contribute nothing and the TPU kernel skips them via ``when`` (the FLOP
savings the §Perf log attributes to this kernel).

Layout: q/k/v are passed as [BH, S, D] (batch and heads pre-flattened, KV
heads broadcast to q heads by the ops wrapper) with block sizes aligned to
the MXU (q_block × d and kv_block × d tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG_INF = -1e30

# Structural contract checked by repro.analysis.kernel_audit: rank-3
# grid (bh, q blocks, kv blocks); no aliased state — the online-softmax
# carries live in VMEM scratch, and the sequential kv axis is what
# makes that carry sound.
AUDIT = {"grid_rank": 3, "aliased_io": False, "sequential_grid": True}


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int | None,
            q_block: int, kv_block: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)

    run = jnp.bool_(True)
    if causal:
        run &= (ki * kv_block) <= (qi * q_block + q_block - 1)
    if window is not None:
        run &= (ki * kv_block + kv_block) > (qi * q_block - window)

    @pl.when(run)
    def _step():
        q = q_ref[0]                       # [q_block, d]
        k = k_ref[0]                       # [kv_block, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, q_block=128,
                    kv_block=128, interpret=None):
    """q [BH, Sq, D], k/v [BH, Skv, D] → [BH, Sq, D]."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    nq = -(-sq // q_block)
    nk = -(-skv // kv_block)
    pq, pk = nq * q_block - sq, nk * kv_block - skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block,
                          kv_block=kv_block, kv_len=skv),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * q_block, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :sq]
