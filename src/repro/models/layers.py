"""Core model layers, pure-JAX (XLA path).

Attention notes:
* `attention` dispatches between a direct path (decode, short sequences), a
  KV-chunked online-softmax path (memory-safe at 32k+ prefill), and a
  *banded* path for sliding-window attention that only touches the W-wide
  KV band (keeps HLO FLOPs ∝ S·W rather than S²).
* GQA is expressed by grouping query heads over KV heads in the einsums, so
  SPMD sharding of the flattened head dim stays clean.

MoE uses sort-based token dispatch (argsort by expert id).  With a
capacity factor the sorted tokens fill E·C slots and overflow is dropped
(FLOPs proportional to top-k, memory O(E·C·d)).  Without one the layer is
dropless: the matmuls are grouped over the experts this chip holds
(``lax.ragged_dot`` over the sorted tokens), however skewed the routing.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x, p):
    if cfg.norm == "rms":
        return rms_norm(x, p["scale"], cfg.rms_eps)
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(cfg, d):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm != "rms":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x [B,S,H,D]; positions [B,S] int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [B,S,D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x1 * sin + x2 * cos
    return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections=(2, 3, 3)):
    """Qwen2-VL multimodal RoPE: the head-dim frequency bands are split into
    (temporal, height, width) sections, each rotated by its own position id.
    ``positions3`` [B,S,3]; for text tokens the three ids coincide."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)  # [D/2]
    n = d // 2
    sec = np.array(sections, dtype=np.float64)
    bounds = np.cumsum(np.round(sec / sec.sum() * n).astype(int))
    bounds[-1] = n
    sel = np.zeros(n, dtype=np.int32)
    sel[bounds[0]:bounds[1]] = 1
    sel[bounds[1]:] = 2
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(jnp.asarray(sel)[None, None, :],
                         positions3.shape[:2] + (n,)).astype(jnp.int32),
        axis=-1)                                                  # [B,S,D/2]
    ang = pos * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x1 * sin + x2 * cos
    return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape).astype(x.dtype)


def position_embed(cfg, q, k, positions):
    if cfg.pos == "rope":
        return (apply_rope(q, positions, cfg.rope_theta),
                apply_rope(k, positions, cfg.rope_theta))
    if cfg.pos == "mrope":
        pos3 = jnp.repeat(positions[..., None], 3, axis=-1)
        return (apply_mrope(q, pos3, cfg.rope_theta),
                apply_mrope(k, pos3, cfg.rope_theta))
    return q, k   # 'none' / 'learned' (added at embedding)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _direct_attention(q, k, v, q_pos, k_pos, causal, window):
    """Materialised-scores path (decode steps / small shapes)."""
    b, s, hq, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = hq // hk
    qg = q.reshape(b, s, hk, g, d)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(d)
    mask = (k_pos >= 0)[None, :]          # -1 marks empty cache slots
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(b, s, hq, v.shape[-1])


def _chunked_attention(q, k, v, q_pos, k_pos, causal, window, kv_chunk):
    """Online-softmax scan over KV chunks: O(S·chunk) live memory, in the
    backward pass too."""
    b, s, hq, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = hq // hk
    qg = q.reshape(b, s, hk, g, d)
    n_chunks = (t + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    dv = v.shape[-1]
    kc = k.reshape(b, n_chunks, kv_chunk, hk, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, hk, dv).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, kv_chunk)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        scores = jnp.einsum("bshgd,bthd->bhgst", qg, kb,
                            preferred_element_type=jnp.float32) / np.sqrt(d)
        mask = jnp.ones((s, kv_chunk), bool)
        if causal:
            mask &= pb[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= pb[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bthd->bhgsd", p.astype(q.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hk, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g, s), jnp.float32)
    a0 = jnp.zeros((b, hk, g, s, dv), jnp.float32)
    # each chunk's scores are recomputed in the backward pass, not kept
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0),
                                  (kc, vc, pc))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, dv)


def _banded_attention(q, k, v, q_pos, k_pos, window, q_chunk):
    """Sliding-window path: each q-chunk only reads its W-wide KV band, so
    compiled FLOPs scale with S·W (not S²)."""
    b, s, hq, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = hq // hk
    band = window + q_chunk
    n_q = (s + q_chunk - 1) // q_chunk
    pad_q = n_q * q_chunk - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-(2**30))
    if t < band:
        k = jnp.pad(k, ((0, 0), (0, band - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, band - t), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, band - t), constant_values=2**30)
        t = band
    qg = q.reshape(b, n_q, q_chunk, hk, g, d).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(n_q, q_chunk)

    def one_chunk(qb, qpb, ci):
        start = jnp.clip((ci + 1) * q_chunk - band, 0, t - band)
        kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        pb = jax.lax.dynamic_slice_in_dim(k_pos, start, band, axis=0)
        scores = jnp.einsum("bshgd,bthd->bhgst", qb, kb,
                            preferred_element_type=jnp.float32) / np.sqrt(d)
        mask = (pb[None, :] <= qpb[:, None]) \
            & (pb[None, :] > qpb[:, None] - window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgst,bthd->bshgd", probs, vb)

    out = jax.lax.map(lambda xs: one_chunk(xs[0], xs[1], xs[2]),
                      (qg, qp, jnp.arange(n_q)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, n_q * q_chunk, hq,
                                                  v.shape[-1])
    return out[:, :s]


def attention(q, k, v, *, q_pos, k_pos, causal=True, window=None,
              kv_chunk=1024, q_chunk=512):
    """q [B,S,Hq,D], k [B,T,Hkv,D], v [B,T,Hkv,Dv], positions int32
    [S]/[T] (absolute) → [B,S,Hq,Dv]."""
    s, t = q.shape[1], k.shape[1]
    if s == 1 or (s * t) <= (2048 * 2048):
        return _direct_attention(q, k, v, q_pos, k_pos, causal, window)
    if window is not None and t > 2 * window:
        return _banded_attention(q, k, v, q_pos, k_pos, window, q_chunk)
    return _chunked_attention(q, k, v, q_pos, k_pos, causal, window,
                              kv_chunk)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg, rng, d, ff, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    std = d ** -0.5
    if cfg.mlp == "swiglu":
        return {
            "w_gate": (jax.random.normal(k1, (d, ff)) * std).astype(dtype),
            "w_up": (jax.random.normal(k2, (d, ff)) * std).astype(dtype),
            "w_down": (jax.random.normal(k3, (ff, d)) * (ff ** -0.5)
                       ).astype(dtype),
        }
    return {
        "w_up": (jax.random.normal(k1, (d, ff)) * std).astype(dtype),
        "w_down": (jax.random.normal(k2, (ff, d)) * (ff ** -0.5)
                   ).astype(dtype),
    }


def mlp(cfg, p, x):
    if cfg.mlp == "swiglu":
        g = jax.nn.silu(x @ p["w_gate"])
        return (g * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch)
# ---------------------------------------------------------------------------

def init_moe(cfg, rng, dtype):
    """Router over all ``n_experts``; weights of the ``experts_held`` this
    chip computes; the shared experts as one MLP of their summed width."""
    d, ff, e, held = cfg.d_model, cfg.d_expert, cfg.n_experts, \
        cfg.experts_held
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    std = d ** -0.5
    p = {
        "router": (jax.random.normal(k0, (d, e)) * std).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (held, d, ff)) * std).astype(dtype),
        "w_up": (jax.random.normal(k2, (held, d, ff)) * std).astype(dtype),
        "w_down": (jax.random.normal(k3, (held, ff, d)) * (ff ** -0.5)
                   ).astype(dtype),
    }
    if cfg.router_score == "sigmoid":
        # noaux_tc's selection bias (DeepSeek-V3's e_score_correction_bias)
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, jax.random.fold_in(rng, 4), d,
                               cfg.n_shared_experts * ff, dtype)
    return p


def route(cfg, p, xf):
    """Each token's ``top_k`` experts [T,k] and their weights [T,k], and
    the load-balancing loss.  Softmax routers renormalise the top-k
    probabilities; sigmoid routers (DeepSeek-V3) pick by score plus the
    selection bias, weigh by the score, renormalise and scale."""
    k = cfg.top_k
    sigmoid = cfg.router_score == "sigmoid"
    logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST if sigmoid
                     else None)                              # [T,E]
    if sigmoid:
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(scores, k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9) \
        * cfg.router_scale
    aux = jnp.zeros((), jnp.float32)
    if cfg.aux_weight:   # Switch-style load balance
        density = jnp.mean(jax.nn.one_hot(idx[:, 0], cfg.n_experts), axis=0)
        aux = cfg.n_experts * jnp.sum(density * jnp.mean(scores, axis=0))
    return idx, vals, aux


def moe(cfg, p, x):
    """x [B,S,d] → ([B,S,d], aux loss, load [held]).

    Routes over all experts and computes the part of the result that the
    held experts ``[expert_offset, expert_offset + experts_held)`` give,
    plus the shared experts.  ``load`` counts the token-expert pairs each
    held expert computed."""
    b, s, d = x.shape
    held, k = cfg.experts_held, cfg.top_k
    tokens = b * s
    xf = x.reshape(tokens, d)

    with jax.named_scope("moe.route"):
        idx, vals, aux = route(cfg, p, xf)
        local = idx.reshape(-1) - cfg.expert_offset           # [T·k]
        key = jnp.where((local >= 0) & (local < held), local, held)
        t_flat = jnp.repeat(jnp.arange(tokens), k)
        order = jnp.argsort(key)          # held experts' pairs first
        se, st, sw = key[order], t_flat[order], vals.reshape(-1)[order]
        counts = jnp.bincount(key, length=held + 1)[:held]

    with jax.named_scope("moe.experts"):
        if cfg.capacity_factor:
            ye, kept, load = _capacity_experts(cfg, p, xf, se, st, counts)
        else:
            ye, kept, load = _grouped_experts(p, xf, se, st, counts)
        # rows outside the held experts' groups are zeroed before they are
        # weighed, so that nothing in them reaches a gradient
        contrib = jnp.where(kept[:, None], ye, 0) \
            * sw[:, None].astype(x.dtype)
        y = jnp.zeros((tokens, d), x.dtype).at[st].add(contrib)
    y = y.reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp(cfg, p["shared"], x)
    return y, aux, load


def _capacity_experts(cfg, p, xf, se, st, counts):
    """Every held expert's tokens in ``cap`` slots, overflow dropped."""
    held, tokens = cfg.experts_held, xf.shape[0]
    cap = int(np.ceil(tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)   # pad to multiple of 8
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(se.shape[0]) - starts[jnp.minimum(se, held - 1)]
    keep = (pos_in_e < cap) & (se < held)
    slot = jnp.where(keep, se * cap + pos_in_e, 0)

    xe = jnp.zeros((held * cap, xf.shape[1]), xf.dtype)
    xe = xe.at[slot].add(jnp.where(keep[:, None], xf[st], 0))
    xe = xe.reshape(held, cap, -1)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])
    return ye.reshape(held * cap, -1)[slot], keep, jnp.minimum(counts, cap)


def _grouped_experts(p, xf, se, st, counts):
    """Dropless: one matmul per weight, grouped over the held experts'
    sorted tokens.  Rows of other experts belong to no group, and
    ``ragged_dot`` leaves them undefined on the TPU, in its gradient too:
    they are zeroed on the way in (so their gradient never reaches a
    token) and on the way out."""
    kept = se < counts.shape[0]
    gs = counts.astype(jnp.int32)
    xs = jnp.where(kept[:, None], xf[st], 0)
    g = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], gs))
    u = jax.lax.ragged_dot(xs, p["w_up"], gs)
    ye = jax.lax.ragged_dot(g * u, p["w_down"], gs)
    return ye, kept, counts
