"""FailRank: PageRank-inspired root-cause ranking on the MCG (§III-D3).

Node update:  s⁽ᵏ⁺¹⁾(v) = (1−λ)·s₀(v) + λ·Σ_{(u,v)∈E} w(u,v)·s⁽ᵏ⁾(u)
Edge update:  l⁽ᵏ⁺¹⁾(u,v) = α·w(u,v) + β·s⁽ᵏ⁾(u) + γ·l⁽ᵏ⁾(u,v)

with the paper's coefficients α=0.1, β=0.3, γ=0.6 and damping λ.  The
iteration stops when ‖v⁽ᵏ⁾−v⁽ᵏ⁻¹⁾‖₁ < ε (=1e-4, ≲17 iterations in the
paper); final scores are softmax-normalised within each MCG level.

Implementation: the MCG is sparse (mesh + DRAM edges), so the propagation
step is a segment-sum gather/scatter; it runs under ``jax.lax.while_loop``
and is jit-compiled.  A Pallas TPU kernel for the fused step lives in
``repro.kernels.failrank_step`` (dense blocked form); this module uses the
XLA path and returns the per-iteration residual trace for the convergence
analysis (Fig 15).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import spans
from .mcg import MCG


@dataclasses.dataclass(frozen=True)
class FailRankParams:
    lam: float = 0.55          # damping λ
    alpha: float = 0.1         # edge: propagation-weight term
    beta: float = 0.3          # edge: source-node term
    gamma: float = 0.6         # edge: momentum term
    eps: float = 1e-4          # L1 convergence tolerance
    max_iters: int = 100


@dataclasses.dataclass
class FailRankResult:
    node_scores: np.ndarray        # softmax-normalised per level
    edge_scores: np.ndarray
    raw_node_scores: np.ndarray    # pre-softmax (for thresholding)
    raw_edge_scores: np.ndarray
    iterations: int
    residuals: np.ndarray          # Δ_k trace (L1), for Fig 15


@partial(jax.jit, static_argnames=("max_iters",))
def _failrank_iterate(s0, l0, w, src, dst, lam, alpha, beta, gamma, eps,
                      max_iters: int):
    n = s0.shape[0]

    def step(s, l):
        contrib = w * s[src]
        s_new = (1.0 - lam) * s0 + lam * jax.ops.segment_sum(
            contrib, dst, num_segments=n)
        l_new = alpha * w + beta * s[src] + gamma * l
        return s_new, l_new

    def cond(carry):
        _, _, k, delta, _ = carry
        return (delta >= eps) & (k < max_iters)

    def body(carry):
        s, l, k, _, res = carry
        s_new, l_new = step(s, l)
        delta = jnp.abs(s_new - s).sum() + jnp.abs(l_new - l).sum()
        res = res.at[k].set(delta)
        return s_new, l_new, k + 1, delta, res

    res0 = jnp.full((max_iters,), jnp.nan, dtype=s0.dtype)
    with jax.named_scope("failrank.iterate"):
        s, l, k, delta, res = jax.lax.while_loop(
            cond, body, (s0, l0, jnp.int32(0),
                         jnp.asarray(jnp.inf, s0.dtype), res0))
    return s, l, k, res


def _softmax_per_level(scores: np.ndarray, levels: np.ndarray) -> np.ndarray:
    out = np.zeros_like(scores)
    for lv in np.unique(levels):
        sel = levels == lv
        x = scores[sel]
        e = np.exp(x - x.max())
        out[sel] = e / e.sum()
    return out


@spans.traced("sloth.failrank.iterate")
def failrank(mcg: MCG, params: FailRankParams = FailRankParams())\
        -> FailRankResult:
    if len(mcg.edge_src) == 0:
        z = np.zeros(mcg.n_nodes)
        return FailRankResult(z, np.zeros(0), mcg.s0.copy(), np.zeros(0), 0,
                              np.zeros(0))
    s, l, k, res = _failrank_iterate(
        jnp.asarray(mcg.s0, dtype=jnp.float32),
        jnp.asarray(mcg.l0, dtype=jnp.float32),
        jnp.asarray(mcg.edge_w, dtype=jnp.float32),
        jnp.asarray(mcg.edge_src), jnp.asarray(mcg.edge_dst),
        params.lam, params.alpha, params.beta, params.gamma, params.eps,
        params.max_iters)
    s = np.asarray(s, dtype=np.float64)
    l = np.asarray(l, dtype=np.float64)
    res = np.asarray(res, dtype=np.float64)
    res = res[~np.isnan(res)]

    node_soft = _softmax_per_level(s, mcg.node_window)
    edge_levels = np.minimum(mcg.edge_src // mcg.mesh.n_cores,
                             mcg.n_windows - 1)
    edge_soft = _softmax_per_level(l, edge_levels)
    spans.count("iters", int(k))
    return FailRankResult(node_soft, edge_soft, s, l, int(k), res)


@spans.traced("sloth.failrank.attribute")
def attribute_links(mcg: MCG, result: FailRankResult,
                    link_theta: np.ndarray | None = None) -> np.ndarray:
    """Fold MCG edge scores back onto physical links.

    Each edge's score is attributed along its XY path; when the EM-inferred
    θ is available the blame concentrates on the path's most anomalous link
    (θ-weighted), otherwise it spreads uniformly.
    """
    n_links = mcg.mesh.n_links
    link_scores = np.zeros(n_links)
    for i, path in enumerate(mcg.edge_link_path):
        if not path:
            continue
        score = result.raw_edge_scores[i]
        if link_theta is not None:
            w = int(min(mcg.edge_src[i] // mcg.mesh.n_cores,
                        mcg.n_windows - 1))
            th = link_theta[w, path]
            share = th / max(th.sum(), 1e-300)
        else:
            share = np.full(len(path), 1.0 / len(path))
        for lid, sh in zip(path, share):
            link_scores[lid] = max(link_scores[lid], score * sh)
    return link_scores
