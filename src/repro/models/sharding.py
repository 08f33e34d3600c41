"""Parameter / activation / cache PartitionSpecs for the production mesh.

Conventions (Megatron-style TP + optional FSDP):
  * tensor-parallel axis "model": attention head projections, MLP ff dim,
    vocab dim of embeddings/logits, MoE expert ff (or the expert dim when
    expert-parallel is enabled);
  * data axes ("pod","data") shard the batch; with ``fsdp=True`` the "data"
    axis additionally shards the non-TP dim of every large parameter
    (ZeRO-3-style, gathered per layer inside the scan);
  * KV caches shard batch over data axes when divisible, otherwise the
    sequence dim (flash-decoding-style partial softmax, handled by SPMD).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _rule(path: tuple[str, ...], shape: tuple[int, ...], *, fsdp, ep,
          embed_mode: str = "dmodel"):
    """PartitionSpec for one parameter leaf (without the stacked-layer axis;
    caller prepends None for the period dimension)."""
    name = path[-1]
    d = fsdp  # alias: the fsdp axis name or None
    if name in ("embed",):
        # d-model sharding keeps token lookups local (a vocab-sharded table
        # forces an SPMD "involuntary full rematerialization" of the gather
        # — measured 100×+ HBM inflation on yi-34b; see EXPERIMENTS §Perf).
        if embed_mode == "vocab":
            return P("model", d)
        return P(d, "model")
    if name in ("lm_head",):
        return P(d, "model")
    if name in ("wq", "wk", "wv"):
        return P(d, "model")
    if name == "wo":
        return P("model", d)
    if name in ("w_gate", "w_up"):
        if len(shape) == 3:      # MoE experts [E, d, ff]
            # expert-parallel: the expert dim takes the model axis, so the
            # per-expert matmuls are unsharded (no TP all-reduce inside)
            return P(ep, d, None) if ep else P(None, d, "model")
        return P(d, "model")
    if name == "w_down":
        if len(shape) == 3:      # [E, ff, d]
            return P(ep, None, d) if ep else P(None, "model", d)
        return P("model", d)
    if name == "router":
        return P(d, None)
    if name == "in_proj":
        return P(d, "model")
    if name == "out_proj":
        return P("model", d)
    if name == "conv_w":
        return P(None, "model")
    if name == "conv_b":
        return P("model")
    # norms, biases, scalars: replicated
    return P(*([None] * len(shape)))


def sanitize(spec: P, shape: tuple[int, ...], mesh) -> P:
    """Drop mesh axes that do not divide their dimension (jit argument
    shardings require exact divisibility)."""
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        out.append(ax if shape[i] % n == 0 else None)
    # pad with None for unspecified trailing dims
    out += [None] * (len(shape) - len(out))
    return P(*out)


def param_specs(params_shape, mesh, *, fsdp_axis: str | None = None,
                expert_parallel: bool = False,
                embed_mode: str = "dmodel"):
    """Pytree of PartitionSpec matching ``jax.eval_shape(init_model, ...)``.

    Leaves under 'periods'/'lead'/'encoder' carry a stacked leading axis
    which is never sharded (scan slices it)."""
    ep = "model" if expert_parallel else None

    def assign(path, leaf):
        keys = tuple(p.key for p in path if hasattr(p, "key"))
        shape = leaf.shape
        stacked = any(k in ("periods", "lead", "encoder") for k in keys)
        if stacked:
            spec = _rule(keys, shape[1:], fsdp=fsdp_axis, ep=ep,
                         embed_mode=embed_mode)
            spec = P(None, *spec)
        else:
            spec = _rule(keys, shape, fsdp=fsdp_axis, ep=ep,
                         embed_mode=embed_mode)
        return sanitize(spec, shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, params_shape)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_spec(mesh, batch: int):
    """Shard the batch over as many data axes as divide it."""
    axes = []
    for a in data_axes(mesh):
        n = mesh.shape[a]
        if batch % n == 0:
            axes.append(a)
            batch //= n
    return tuple(axes)


def token_specs(mesh, batch: int):
    return P(batch_spec(mesh, batch) or None, None)


def cache_specs(cfg, cache_shape, mesh, batch: int):
    """Specs for the cache pytree (leading period axis on every leaf)."""
    model_n = mesh.shape["model"]
    dp = batch_spec(mesh, batch)
    heads_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % model_n == 0
    shard_seq_model = not heads_ok

    def assign(path, leaf):
        keys = tuple(p.key for p in path if hasattr(p, "key"))
        name = keys[-1]
        if name in ("k", "v"):
            # [np, B, T, Hkv, Dh]
            if dp:
                spec = P(None, dp, "model" if shard_seq_model else None,
                         None if shard_seq_model else "model", None)
            else:
                # batch of 1 (long-context): shard sequence over data axes
                spec = P(None, None, data_axes(mesh) or None,
                         "model" if heads_ok else None, None)
        elif name == "pos_tab":
            spec = P(None, None)
        elif name == "pos":
            spec = P(None)
        elif name == "conv":
            spec = P(None, dp or None, None, "model")
        elif name == "ssm":
            spec = P(None, dp or None, "model", None, None)
        else:
            spec = P(*([None] * leaf.ndim))
        return sanitize(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, cache_shape)
