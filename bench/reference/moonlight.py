"""Plain reference of a DeepSeek-V3-style decoder's training step
(Moonlight-16B-A3B), in jax.numpy, for one chip's share of it.

It imports nothing of the program; of the Llama reference it takes only
the generic parts (the token stream ``batch``, ``_adamw`` and
``_Frozen``).  It makes the benchmark's weights from the seed and
computes the loss, the gradients and AdamW's update in float32 with
``jax.default_matmul_precision("highest")``, following DeepSeek-V3's
published layer equations:

* multi-head latent attention in its training form: ``q = h W_q``
  (queries projected directly, ``q_lora_rank`` null); ``c, k_pe =
  split(h W_kva)``; ``c = RMSNorm(c)``; ``k_nope, v = split(c W_kvb)``;
  RoPE on ``q_pe`` and on the one ``k_pe`` all heads share, rotating
  interleaved pairs as DeepSeek-V3's reference code does; scores scaled
  by ``1/sqrt(qk_nope + qk_rope)`` under a causal softmax; out ``o W_o``;
* the router: ``s = sigmoid(h W_r)`` over all ``router_experts``, the
  top ``num_experts_per_tok`` of ``s + bias`` chosen (noaux_tc with one
  group), weighed by ``s``, normalised over the chosen and scaled by
  ``routed_scaling_factor``;
* each MoE layer adds, for every token, weight x SwiGLU expert over the
  chosen experts it holds, ``[expert_offset, expert_offset +
  n_routed_experts)``, and the shared experts (one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``).  Here every held expert
  is computed on every token and weighed by a dense weight matrix that
  is zero where the token did not choose it: no sorting, no grouping, no
  token dropped;
* ``first_k_dense_replace`` leading layers with a SwiGLU of width
  ``intermediate_size``; an untied output head.

Each layer is a ``jax.checkpoint``; attention runs in blocks of queries,
the held experts one after another and the loss in blocks of
positions, each a ``jax.checkpoint`` too, so that no ``heads x seq x
seq`` score matrix, no expert's activations beside another's and no
whole ``seq x vocab`` logit matrix is live at once: the weights, the
gradients and AdamW's two moments of this share take 10.7 GB of the
chip's 16.

The weights are laid out as the program keeps them: ``embed``,
``lm_head``, ``final_norm.scale``, the leading dense layers stacked under
``lead.slot0`` and the MoE layers under ``periods.slot0``, the held
experts stacked on a second axis.  Weights are normal with the program's
scales (0.02 for the embedding and the head, ``fan_in ** -0.5`` for the
projections), norms one, the router's selection bias zero.

``dtype=jnp.bfloat16`` runs the same steps with the weights, the
activations and AdamW's state in bfloat16: the control that the
comparison must fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference.lm import _adamw, _Frozen, batch

#: positions per block of attention's queries and of the loss
Q_BLOCK = 512


def _block(s: int) -> int:
    """Positions per block: the largest divisor of both ``s`` and
    ``Q_BLOCK``."""
    return math.gcd(s, Q_BLOCK)

# ---------------------------------------------------------------------------
# the seed's weights
# ---------------------------------------------------------------------------


def _sizes(m: dict) -> dict:
    h = m["num_attention_heads"]
    return dict(d=m["hidden_size"], h=h, r=m["kv_lora_rank"],
                nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
                dv=m["v_head_dim"], v=m["vocab_size"])


def shapes(m: dict) -> dict:
    """The weights' shapes, in the program's layout."""
    z = _sizes(m)
    d, h, r = z["d"], z["h"], z["r"]
    n_lead = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_lead
    held, e = m["n_routed_experts"], m["router_experts"]
    f, ff = m["moe_intermediate_size"], m["intermediate_size"]
    shared = m["n_shared_experts"] * f

    def attn(n):
        return {"wq": (n, d, h * (z["nope"] + z["rope"])),
                "wkv_a": (n, d, r + z["rope"]),
                "kv_norm": {"scale": (n, r)},
                "wkv_b": (n, r, h * (z["nope"] + z["dv"])),
                "wo": (n, h * z["dv"], d)}

    def mlp(n, width):
        return {"w_gate": (n, d, width), "w_up": (n, d, width),
                "w_down": (n, width, d)}

    return {
        "embed": (z["v"], d),
        "lm_head": (d, z["v"]),
        "final_norm": {"scale": (d,)},
        "lead": {"slot0": {"norm1": {"scale": (n_lead, d)},
                           "attn": attn(n_lead),
                           "norm2": {"scale": (n_lead, d)},
                           "mlp": mlp(n_lead, ff)}},
        "periods": {"slot0": {
            "norm1": {"scale": (n_moe, d)},
            "attn": attn(n_moe),
            "norm2": {"scale": (n_moe, d)},
            "moe": {"router": (n_moe, d, e), "router_bias": (n_moe, e),
                    "w_gate": (n_moe, held, d, f),
                    "w_up": (n_moe, held, d, f),
                    "w_down": (n_moe, held, f, d),
                    "shared": mlp(n_moe, shared)}}},
    }


def _std(path: str, shape) -> float | None:
    """Initial scale of a weight; ``None`` for a norm (ones) or the
    selection bias (zeros)."""
    if path.endswith("scale") or path.endswith("router_bias"):
        return None
    if path in ("embed", "lm_head"):
        return 0.02
    return shape[-2] ** -0.5


def init_params(m: dict, key, dtype=jnp.float32) -> dict:
    """The benchmark's weights from ``key``; one array per leaf."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = jax.tree_util.keystr(path, simple=True, separator=".")
        std = _std(name, shape)
        if std is not None:
            leaves.append((jax.random.normal(k, shape, jnp.float32) * std)
                          .astype(dtype))
        else:
            fill = 0.0 if name.endswith("router_bias") else 1.0
            leaves.append(jnp.full(shape, fill, dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


make_params = jax.jit(init_params, static_argnums=(0, 2))


def make_params_from_seed(m: dict, seed: int, dtype=jnp.float32) -> dict:
    """The weights of ``seed``, made on the device in one jitted call."""
    return make_params(_Frozen(m), jax.random.PRNGKey(seed), dtype)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate interleaved pairs of ``x`` [B, S, H, D] by position."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def attention(m: dict, p: dict, x):
    """Latent attention of ``x`` [B, S, d], causal, in query blocks."""
    z = _sizes(m)
    b, s, _ = x.shape
    h, r, nope, rope = z["h"], z["r"], z["nope"], z["rope"]
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
    ckv = x @ p["wkv_a"]
    c = _rms(ckv[..., :r], p["kv_norm"]["scale"], m["rms_norm_eps"])
    kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + z["dv"])
    theta = m["rope_theta"]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_pe = _rope(ckv[:, :, None, r:], theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    v = kv[..., nope:]

    @jax.checkpoint
    def block(args):
        qb, q0 = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) \
            / math.sqrt(nope + rope)
        rows = q0 + jnp.arange(qb.shape[1])
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr.astype(v.dtype), v)

    size = _block(s)
    qs = q.reshape(b, s // size, size, h, nope + rope).swapaxes(0, 1)
    o = jax.lax.map(block, (qs, jnp.arange(s // size) * size))
    return o.swapaxes(0, 1).reshape(b, s, h * z["dv"]) @ p["wo"]


def route(m: dict, p: dict, x):
    """Dense routing weights [T, router_experts]: each token's chosen
    experts' normalised, scaled sigmoid scores, zero elsewhere."""
    scores = jax.nn.sigmoid((x @ p["router"].astype(x.dtype))
                            .astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           m["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1],
                                    dtype=jnp.float32), axis=1)
    w = scores * chosen
    return w / w.sum(-1, keepdims=True) * m["routed_scaling_factor"]


def moe(m: dict, p: dict, x):
    """The held experts' part of the MoE output of ``x`` [T, d], plus the
    shared experts."""
    w = route(m, p, x)
    off, held = m["expert_offset"], m["n_routed_experts"]
    w = w[:, off:off + held].astype(x.dtype)                  # [T, held]

    @jax.checkpoint
    def expert(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    sh = p["shared"]
    y, _ = jax.lax.scan(expert, _swiglu(x, **sh),
                        (p["w_gate"], p["w_up"], p["w_down"], w.T))
    return y


def loss(m: dict, p: dict, tokens, dtype=jnp.float32):
    """Mean next-token cross-entropy of ``tokens`` [B, S], with the weights
    and activations in ``dtype``."""
    p = jax.tree.map(lambda w: w.astype(dtype), p)
    eps = m["rms_norm_eps"]
    b, s = tokens.shape
    x = p["embed"][tokens]

    def layer(ffn):
        @jax.checkpoint
        def apply(h, lp):
            h = h + attention(m, lp["attn"],
                              _rms(h, lp["norm1"]["scale"], eps))
            a = _rms(h, lp["norm2"]["scale"], eps)
            return h + ffn(lp, a), None
        return apply

    dense = layer(lambda lp, a: _swiglu(a, **lp["mlp"]))
    sparse = layer(lambda lp, a: moe(m, lp["moe"], a.reshape(b * s, -1))
                   .reshape(b, s, -1))
    x, _ = jax.lax.scan(dense, x, p["lead"]["slot0"])
    x, _ = jax.lax.scan(sparse, x, p["periods"]["slot0"])
    x = _rms(x, p["final_norm"]["scale"], eps)

    # the next-token pairs in blocks of positions; the last position has
    # no next token and weighs nothing
    n = s // _block(s)
    tgt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    weight = (jnp.arange(s) < s - 1).astype(jnp.float32)

    @jax.checkpoint
    def nll(args):
        xb, tb, wb = args
        logits = (xb @ p["lm_head"]).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * wb)

    per_block = jax.lax.map(nll, (
        x.reshape(b, n, -1, x.shape[-1]).swapaxes(0, 1),
        tgt.reshape(b, n, -1).swapaxes(0, 1), weight.reshape(n, 1, -1)))
    return jnp.sum(per_block) / (b * (s - 1))


# ---------------------------------------------------------------------------
# the three reference steps
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> jnp.ndarray:
    """The L2 norm of every leaf slice, in the tree's leaf order: a leaf
    of a stacked layer (under ``lead`` or ``periods``) gives one norm per
    layer, the others one each."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    parts = []
    for path, x in flat:
        x = x.astype(jnp.float32)
        name = jax.tree_util.keystr(path, simple=True, separator=".")
        if name.startswith(("lead.", "periods.")):
            parts.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                range(1, x.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnums=(4, 5),
                   donate_argnums=(0, 1, 2, 3))
def _adamw_step(params, grads, mom, vel, step, o):
    """``lm``'s AdamW, compiled with its state updated in place: the
    weights, two moments and gradients of a model this size fill most of
    a chip."""
    return _adamw(params, grads, mom, vel, step, o)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


@jax.jit
def _delta_norms(params, start):
    return leaf_norms(jax.tree.map(lambda p, q: p.astype(jnp.float32) - q,
                                   params, start))


def run_reference(m: dict, o: dict, seed: int, rows: int, seq: int,
                  steps: int = 3, block: int = 2, dtype=jnp.float32,
                  use_rows: int | None = None) -> dict:
    """``steps`` AdamW steps of the seed's weights on the seed's first
    batches, with weights, activations and AdamW's state in ``dtype``.
    Gradients are taken over blocks of ``block`` rows and averaged.
    ``use_rows`` keeps only the first rows of each batch, the mean taken
    over them (the fault of half a batch left out).  Returns the losses,
    the norms of the first (clipped) gradient and of the weights' change
    after ``steps`` steps, by leaf slice."""
    use_rows = use_rows or rows
    frozen, opt = _Frozen(m), _Frozen(o)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda x: x.astype(dtype),
                              make_params_from_seed(m, seed))
        mom = jax.tree.map(jnp.zeros_like, params)
        vel = jax.tree.map(jnp.zeros_like, params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t, w: loss(frozen, p, t, dtype) * w))
        losses, g1 = [], None
        for step in range(1, steps + 1):
            toks = batch(seed, step - 1, rows, seq, m["vocab_size"])[:use_rows]
            tot_l, grads = 0.0, None
            for r in range(0, use_rows, block):
                part = toks[r:r + block]
                w = jnp.asarray(part.shape[0] / use_rows, dtype)
                lv, g = grad_fn(params, jnp.asarray(part), w)
                tot_l += float(lv)
                grads = g if grads is None else _add(grads, g)
                del g
            losses.append(tot_l)
            params, mom, vel, g_clip = _adamw_step(params, grads, mom, vel,
                                                   step, opt)
            del grads
            if step == 1:
                g1 = np.asarray(leaf_norms(g_clip))
            del g_clip
        del mom, vel
        dp = np.asarray(_delta_norms(params, make_params_from_seed(m, seed)))
    return {"losses": losses, "grad_norms": g1, "delta_norms": dp}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, by the convention of
    ``bench/flops.py``: 6 per multiply-accumulate weight a token touches
    (2 forward, 4 backward), plus 6 x seq x heads x (qk_nope + qk_rope +
    v_head_dim) per layer for the scores and their weighted sum over the
    whole ``seq x seq`` square; recomputation not counted, the embedding
    lookup not a multiplication.  A token touches the held experts at
    their expected load: ``num_experts_per_tok x n_routed_experts /
    router_experts`` experts (0.75 for 6 of 64 with 8 held)."""
    z = _sizes(m)
    d, h, r = z["d"], z["h"], z["r"]
    n_lead = m["first_k_dense_replace"]
    n = m["num_hidden_layers"]
    f = m["moe_intermediate_size"]
    attn = (d * h * (z["nope"] + z["rope"]) + d * (r + z["rope"])
            + r * h * (z["nope"] + z["dv"]) + h * z["dv"] * d)
    experts = m["num_experts_per_tok"] * m["n_routed_experts"] \
        / m["router_experts"]
    moe = d * m["router_experts"] + (experts + m["n_shared_experts"]) \
        * 3 * d * f
    macs = n * attn + n_lead * 3 * d * m["intermediate_size"] \
        + (n - n_lead) * moe + d * z["v"]
    scores = n * seq * h * (z["nope"] + z["rope"] + z["dv"])
    return (6.0 * macs + 6.0 * scores) * batch * seq
