"""Pallas TPU kernel: fused FailRank node+link update (one iteration).

Dense MCG form (the MCG of a pod window stack is ≤ a few thousand nodes, so
the dense matrix fits VMEM in column stripes):

    s'[v]   = (1−λ)·s0[v] + λ·Σ_u W[u,v]·s[u]          (MXU matvec)
    L'[u,v] = α·W[u,v] + β·s[u] + γ·L[u,v]             (VPU elementwise)

Grid over column stripes: each step loads W[:, j·C:(j+1)·C] and L[:, ...]
once from HBM and produces both outputs in a single pass — the fusion is
the point (the XLA path reads W twice).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

# Structural contract checked by repro.analysis.kernel_audit: rank-1
# grid over column stripes; each stripe writes disjoint output blocks
# and no state is aliased across steps.
AUDIT = {"grid_rank": 1, "aliased_io": False, "sequential_grid": True}


def _kernel(w_ref, l_ref, s_ref, s0_ref, s_out_ref, l_out_ref, *,
            lam: float, alpha: float, beta: float, gamma: float):
    w = w_ref[:]                # [n, C]
    s = s_ref[:]                # [n, 1]
    s0 = s0_ref[0]              # [C]
    contrib = jax.lax.dot_general(s, w, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    s_out_ref[0] = (1.0 - lam) * s0 + lam * contrib[0]
    l_out_ref[:] = alpha * w + beta * s + gamma * l_ref[:]


@functools.partial(jax.jit, static_argnames=("lam", "alpha", "beta",
                                             "gamma", "col_block",
                                             "interpret"))
def failrank_step(w, l, s, s0, *, lam=0.55, alpha=0.1, beta=0.3,
                  gamma=0.6, col_block: int = 128,
                  interpret: bool | None = None):
    """w/l [n,n] (w[u,v] = propagation weight), s/s0 [n] → (s', L')."""
    n = w.shape[0]
    nb = -(-n // col_block)
    pad = nb * col_block - n
    if pad:
        w = jnp.pad(w, ((0, pad), (0, pad)))
        l = jnp.pad(l, ((0, pad), (0, pad)))
        s = jnp.pad(s, (0, pad))
        s0 = jnp.pad(s0, (0, pad))
    npad = n + pad

    s_new, l_new = pl.pallas_call(
        functools.partial(_kernel, lam=lam, alpha=alpha, beta=beta,
                          gamma=gamma),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((npad, col_block), lambda j: (0, j)),
            pl.BlockSpec((npad, col_block), lambda j: (0, j)),
            pl.BlockSpec((npad, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, col_block), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, col_block), lambda j: (0, j)),
            pl.BlockSpec((npad, col_block), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
            jax.ShapeDtypeStruct((npad, npad), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(w, l, s.reshape(npad, 1), s0.reshape(1, npad))
    return s_new[0, :n], l_new[:n, :n]
