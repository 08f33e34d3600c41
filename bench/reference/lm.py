"""Plain reference of a Llama-style decoder's training step, in jax.numpy.

It imports nothing of the program.  It makes the benchmark's weights and
tokens from the seed, and it computes the loss, the gradients and AdamW's
update in float32 with ``jax.default_matmul_precision("highest")``,
following the published architecture (Llama: RMSNorm, rotary attention
with grouped key/value heads, SwiGLU MLP, tied embeddings).  Where the
program departs from the published model the reference follows the
program, and the configuration file says so: RoPE rotates interleaved
pairs ``(2i, 2i+1)`` (Hugging Face rotates halves), and RMSNorm's epsilon
is ``rms_norm_eps`` as the file states it.

The weights are laid out as the program keeps them: ``embed``,
``final_norm.scale`` and ``periods.slot0.{norm1, attn, norm2, mlp}``
with the layers stacked on a leading axis.  Weights are normal with the
program's scales (0.02 for the embedding, ``fan_in ** -0.5`` for the
projections), norms one.

``dtype=jnp.bfloat16`` runs the same steps with the weights, the
activations and AdamW's state in bfloat16: the control that the
comparison must fail.

It provides the whole interface of a training reference
(``bench/drivers/train.py``); its model FLOPs per step are
``bench/flops.py``'s, which counts this architecture.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from flops import train_flops_per_step  # noqa: F401  (the interface's)

# ---------------------------------------------------------------------------
# the seed's weights and tokens
# ---------------------------------------------------------------------------


def shapes(m: dict) -> dict:
    """The weights' shapes, in the program's layout."""
    d, ff, v, n = (m["hidden_size"], m["intermediate_size"],
                   m["vocab_size"], m["num_hidden_layers"])
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    hq, hk = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {
        "embed": (v, d),
        "final_norm": {"scale": (d,)},
        "periods": {"slot0": {
            "norm1": {"scale": (n, d)},
            "attn": {"wq": (n, d, hq), "wk": (n, d, hk), "wv": (n, d, hk),
                     "wo": (n, hq, d)},
            "norm2": {"scale": (n, d)},
            "mlp": {"w_gate": (n, d, ff), "w_up": (n, d, ff),
                    "w_down": (n, ff, d)},
        }},
    }


def _std(path: str, shape) -> float | None:
    """Initial scale of a weight; ``None`` for a norm (ones)."""
    if path.endswith("scale"):
        return None
    if path == "embed":
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
        if std is None:
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32) * std)
                          .astype(dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


make_params = jax.jit(init_params, static_argnums=(0, 2))


def make_params_from_seed(m: dict, seed: int, dtype=jnp.float32) -> dict:
    """The weights of ``seed``, made on the device in one jitted call."""
    return make_params(_Frozen(m), jax.random.PRNGKey(seed), dtype)


class _Frozen(dict):
    """A hashable view of a configuration, for jit's static arguments."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + _GOLDEN).astype(np.uint64)
    z = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    """Tokens of one step, Zipf-like as tokenized text is: each draw picks
    an octave ``k`` below the vocabulary's bit length, then an id in
    ``[2^k - 1, 2^(k+1) - 1)`` folded into the vocabulary (the arithmetic
    of the program's synthetic stream).  A pure function of (seed, step)."""
    with np.errstate(over="ignore"):
        base = (np.uint64(seed) << np.uint64(32)) + np.uint64(step)
        idx = np.arange(rows * seq, dtype=np.uint64) \
            + base * np.uint64(rows * seq)
        h = _splitmix64(idx)
        octave = (h >> np.uint64(32)) % np.uint64(int(vocab).bit_length())
        width = np.uint64(1) << octave
        toks = (width - np.uint64(1) + (h & np.uint64(0xFFFFFFFF)) % width) \
            % np.uint64(vocab)
    return toks.astype(np.int32).reshape(rows, seq)


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


def loss(m: dict, p: dict, tokens, dtype=jnp.float32):
    """Mean next-token cross-entropy of ``tokens`` [B, S], with the weights
    and activations in ``dtype``."""
    p = jax.tree.map(lambda w: w.astype(dtype), p)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    nh, nk = m["num_attention_heads"], m["num_key_value_heads"]
    b, s = tokens.shape
    x = p["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, lp):
        a = _rms(h, lp["norm1"]["scale"], eps)
        q = (a @ lp["attn"]["wq"]).reshape(b, s, nh, -1)
        k = (a @ lp["attn"]["wk"]).reshape(b, s, nk, -1)
        v = (a @ lp["attn"]["wv"]).reshape(b, s, nk, -1)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, nh // nk, axis=2)
        v = jnp.repeat(v, nh // nk, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            / math.sqrt(q.shape[-1])
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(v.dtype), v)
        h = h + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        a = _rms(h, lp["norm2"]["scale"], eps)
        g = jax.nn.silu(a @ lp["mlp"]["w_gate"]) * (a @ lp["mlp"]["w_up"])
        return h + g @ lp["mlp"]["w_down"], None

    x, _ = jax.lax.scan(layer, x, p["periods"]["slot0"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    logits = (x @ p["embed"].T)[:, :-1].astype(jnp.float32)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ---------------------------------------------------------------------------
# the optimizer and the three reference steps
# ---------------------------------------------------------------------------

def lr_at(step: int, o: dict) -> float:
    """AdamW's learning rate at 1-based ``step``: linear warm-up, then a
    cosine to ``min_lr_frac`` at ``total_steps``."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return o["lr"] * warm * frac


def leaf_norms(tree) -> jnp.ndarray:
    """The L2 norm of every leaf slice, in the tree's leaf order: a stacked
    layer weight gives one norm per layer, the others one each."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    parts = []
    for path, x in flat:
        x = x.astype(jnp.float32)
        if jax.tree_util.keystr(path, simple=True,
                                separator=".").startswith("periods."):
            parts.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                range(1, x.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(parts)


def run_reference(m: dict, o: dict, seed: int, rows: int, seq: int,
                  steps: int = 3, block: int = 2, dtype=jnp.float32,
                  use_rows: int | None = None) -> dict:
    """``steps`` AdamW steps of the seed's weights on the seed's first
    batches, with weights, activations and AdamW's state in ``dtype``.
    Gradients are taken over blocks of ``block`` rows and averaged, so the
    activations of the whole batch are never live at once.
    ``use_rows`` keeps only the first rows of each batch, the mean taken
    over them (the fault of half a batch left out).  Returns the losses,
    the norms of the first (clipped) gradient and of the weights' change
    after ``steps`` steps, by leaf slice."""
    use_rows = use_rows or rows
    with jax.default_matmul_precision("highest"):
        start = make_params_from_seed(m, seed)
        params = jax.tree.map(lambda x: x.astype(dtype), start)
        mom = jax.tree.map(jnp.zeros_like, params)
        vel = jax.tree.map(jnp.zeros_like, params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t: loss(_Frozen(m), p, t, dtype)))
        losses, g1 = [], None
        for step in range(1, steps + 1):
            toks = batch(seed, step - 1, rows, seq, m["vocab_size"])[:use_rows]
            tot_l, grads = 0.0, None
            for r in range(0, use_rows, block):
                lv, g = grad_fn(params, jnp.asarray(toks[r:r + block]))
                w = toks[r:r + block].shape[0] / use_rows
                tot_l += float(lv) * w
                g = jax.tree.map(lambda x: x * jnp.asarray(w, x.dtype), g)
                grads = g if grads is None else jax.tree.map(jnp.add, grads,
                                                             g)
            losses.append(tot_l)
            params, mom, vel, g_clip = _adamw(params, grads, mom, vel,
                                              step, o)
            if step == 1:
                g1 = np.asarray(leaf_norms(g_clip))
            del grads, g_clip
        dp = np.asarray(_delta_norms(params, start))
    return {"losses": losses, "grad_norms": g1, "delta_norms": dp}


@jax.jit
def _delta_norms(params, start):
    return leaf_norms(jax.tree.map(lambda p, q: p.astype(jnp.float32) - q,
                                   params, start))


def _adamw(params, grads, mom, vel, step: int, o: dict):
    """AdamW with clipping by the global norm, in the weights' dtype."""
    b1, b2 = o["b1"], o["b2"]
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale.astype(x.dtype), grads)
    mom = jax.tree.map(lambda mm, x: b1 * mm + (1 - b1) * x, mom, g)
    vel = jax.tree.map(lambda vv, x: b2 * vv + (1 - b2) * x * x, vel, g)
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    lr = lr_at(step, o)
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * ((mm / bc1) / (jnp.sqrt(vv / bc2)
                                                  + o["eps"])
                                    + o["weight_decay"] * p),
        params, mom, vel)
    return params, mom, vel, g
