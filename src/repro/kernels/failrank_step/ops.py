"""Public API: dense FailRank iteration over an MCG via the Pallas kernel.

``failrank_dense(mcg, params)`` mirrors ``repro.core.failrank.failrank``
(COO/XLA path) and is validated against it in the kernel tests.
"""

from __future__ import annotations

import numpy as np

from ...core.failrank import FailRankParams, _softmax_per_level
from ...core.mcg import MCG
from .kernel import failrank_step
from .ref import failrank_step_ref


def mcg_dense(mcg: MCG):
    n = mcg.n_nodes
    w = np.zeros((n, n), np.float32)
    l = np.zeros((n, n), np.float32)
    w[mcg.edge_src, mcg.edge_dst] = mcg.edge_w
    l[mcg.edge_src, mcg.edge_dst] = mcg.l0
    return w, l


def failrank_dense(mcg: MCG, params: FailRankParams = FailRankParams(),
                   impl: str = "pallas", interpret: bool | None = None):
    """Returns (node_scores softmaxed, raw s, raw dense L, iterations)."""
    import jax.numpy as jnp
    w, l = mcg_dense(mcg)
    w, l = jnp.asarray(w), jnp.asarray(l)
    s = jnp.asarray(mcg.s0, jnp.float32)
    s0 = s
    it = 0
    for it in range(1, params.max_iters + 1):
        if impl == "pallas":
            s_new, l_new = failrank_step(
                w, l, s, s0, lam=params.lam, alpha=params.alpha,
                beta=params.beta, gamma=params.gamma, interpret=interpret)
        else:
            s_new, l_new = failrank_step_ref(
                w, l, s, s0, lam=params.lam, alpha=params.alpha,
                beta=params.beta, gamma=params.gamma)
        delta = float(abs(s_new - s).sum() + abs(l_new - l).sum())
        s, l = s_new, l_new
        if delta < params.eps:
            break
    node_soft = _softmax_per_level(np.asarray(s, np.float64),
                                   mcg.node_window)
    return node_soft, np.asarray(s), np.asarray(l), it
