"""Step builders + abstract input specs for every (arch × shape) cell.

``build_cell`` returns (step_fn, input ShapeDtypeStructs, in_shardings,
out_shardings) for one cell, ready for ``jax.jit(...).lower(...)`` — used
by both the dry-run and the real launchers.

Shapes (assignment):
  train_4k     seq 4096,   global batch 256   → train_step (fwd+bwd+AdamW)
  prefill_32k  seq 32768,  global batch 32    → prefill (fills KV cache)
  decode_32k   seq 32768,  global batch 128   → serve_step (1 token, cache)
  long_500k    seq 524288, global batch 1     → serve_step, sub-quadratic
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import sharding as S
from ..models import transformer as T
from ..optim import adamw

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


@dataclasses.dataclass(frozen=True)
class CellPlan:
    """Tunable distribution knobs for one cell (the hillclimb levers)."""
    fsdp: bool = False
    expert_parallel: bool = False
    grad_accum: int = 1
    remat: bool = True
    param_dtype: Any = jnp.bfloat16
    opt_state_dtype: Any = jnp.float32
    seq_shard_activations: bool = False   # Megatron-SP style boundary shard
    embed_mode: str = "vocab"             # 'dmodel' was refuted (see §Perf)
    pin_activations: bool = False         # residual-stream constraints
    pad_vocab: bool = False               # pad vocab to a shardable multiple


def default_plan(cfg: ArchConfig, shape: str) -> CellPlan:
    """Baseline plan: FSDP + bf16 optimizer for the ≥30B models, gradient
    accumulation sized so boundary activations fit."""
    big = cfg.param_count() > 20e9
    huge = cfg.param_count() > 100e9
    accum = 1
    if SHAPES[shape]["kind"] == "train":
        # per-device boundary activation budget ≈ b_loc·S·d·2B per period
        accum = 8 if big else 4
    return CellPlan(fsdp=big, expert_parallel=False, grad_accum=accum,
                    opt_state_dtype=jnp.bfloat16 if huge else jnp.float32)


def skip_reason(cfg: ArchConfig, shape: str) -> str | None:
    if shape == "long_500k" and not cfg.sub_quadratic:
        return ("pure full-attention arch: 500k dense decode is the "
                "quadratic case the assignment excludes")
    return None


# ---------------------------------------------------------------------------
# loss / steps
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, tokens, enc_frames=None, remat=True):
    """(loss with the weighted aux loss, (cross-entropy, expert load))."""
    logits, aux, load = T.forward_train(cfg, params, tokens,
                                        enc_frames=enc_frames, remat=remat)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1)[..., 0]
    ce = (logz - gold).mean()
    return ce + cfg.aux_weight * aux, (ce, load)


def make_train_step(cfg, plan: CellPlan, opt_cfg=None):
    """The step: (params, opt_state, tokens[, enc_frames]) → (params,
    opt_state, loss, grad_norm), and for MoE configs the token-expert
    pairs each held expert computed per MoE layer [MoE layers, held]."""
    opt_cfg = opt_cfg or adamw.AdamWConfig(state_dtype=plan.opt_state_dtype)

    def train_step(params, opt_state, tokens, enc_frames=None):
        def micro_loss(p, toks, frames):
            return loss_fn(cfg, p, toks, frames, remat=plan.remat)

        if plan.grad_accum > 1:
            a = plan.grad_accum
            b = tokens.shape[0] // a
            toks = tokens.reshape(a, b, tokens.shape[1])
            frames = None
            if enc_frames is not None:
                frames = enc_frames.reshape(a, b, *enc_frames.shape[1:])

            def acc(carry, xs):
                g_sum, l_sum, ld_sum = carry
                tb = xs[0]
                fb = xs[1] if enc_frames is not None else None
                (l, (_, ld)), g = jax.value_and_grad(
                    micro_loss, has_aux=True)(params, tb, fb)
                return (jax.tree.map(jnp.add, g_sum, g), l_sum + l,
                        jax.tree.map(jnp.add, ld_sum, ld)), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            ld0 = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(micro_loss, params, toks[0],
                               None if frames is None else frames[0])[1][1])
            if enc_frames is None:
                (grads, loss, load), _ = jax.lax.scan(
                    lambda c, t: acc(c, (t, None)), (g0, 0.0, ld0), toks)
            else:
                (grads, loss, load), _ = jax.lax.scan(
                    acc, (g0, 0.0, ld0), (toks, frames))
            grads = jax.tree.map(lambda g: g / a, grads)
            loss = loss / a
        else:
            (loss, (_, load)), grads = jax.value_and_grad(
                micro_loss, has_aux=True)(params, tokens, enc_frames)

        new_params, new_opt, stats = adamw.apply(params, grads, opt_state,
                                                 opt_cfg)
        out = (new_params, new_opt, loss, stats["grad_norm"])
        return out if load is None else out + (load,)

    return train_step


def make_prefill_step(cfg, plan: CellPlan, seq: int, batch: int):
    def prefill_step(params, tokens, enc_frames=None):
        cache = T.init_cache(cfg, batch, seq, dtype=plan.param_dtype)
        last, cache, memory = T.prefill(cfg, params, tokens, cache,
                                        enc_frames=enc_frames,
                                        remat=plan.remat)
        out = (last, cache)
        return out + ((memory,) if cfg.enc_dec else ())
    return prefill_step


def make_decode_step(cfg, plan: CellPlan):
    def serve_step(params, tokens, cache, pos, memory=None):
        logits, cache = T.decode_step(cfg, params, tokens, cache, pos,
                                      memory=memory)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return next_tok.astype(jnp.int32), logits, cache
    return serve_step


# ---------------------------------------------------------------------------
# cell assembly
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def params_shape(cfg, plan: CellPlan):
    return jax.eval_shape(
        partial(T.init_model, cfg, dtype=plan.param_dtype),
        jax.random.PRNGKey(0))


def input_specs(cfg: ArchConfig, shape: str, plan: CellPlan):
    """Abstract ShapeDtypeStructs for every model input of this cell."""
    sh = SHAPES[shape]
    b, s = sh["batch"], sh["seq"]
    specs = {}
    if sh["kind"] in ("train", "prefill"):
        specs["tokens"] = _sds((b, s), jnp.int32)
        if cfg.enc_dec:
            specs["enc_frames"] = _sds((b, cfg.n_frames, cfg.d_model),
                                       plan.param_dtype)
    else:
        specs["tokens"] = _sds((b, 1), jnp.int32)
        specs["cache"] = jax.eval_shape(
            partial(T.init_cache, cfg, b, s, dtype=plan.param_dtype))
        specs["pos"] = _sds((), jnp.int32)
        if cfg.enc_dec:
            specs["memory"] = _sds((b, cfg.n_frames, cfg.d_model),
                                   plan.param_dtype)
    return specs


def build_cell(cfg: ArchConfig, shape: str, mesh, plan: CellPlan | None
               = None):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    plan = plan or default_plan(cfg, shape)
    if plan.pad_vocab and cfg.vocab % 128:
        # unshardable vocab forces a replicated embedding/lm_head — pad to
        # the next multiple of 128 (tokens never index the padding)
        cfg = dataclasses.replace(cfg, vocab=-(-cfg.vocab // 128) * 128)
    sh = SHAPES[shape]
    b = sh["batch"]
    from ..models import policy
    if plan.pin_activations:
        policy.set_policy(S.batch_spec(mesh, b) or None, "model",
                          seq_shard=plan.seq_shard_activations)
    else:
        policy.clear_policy()
    pshape = params_shape(cfg, plan)
    pspec = S.param_specs(pshape, mesh,
                          fsdp_axis="data" if plan.fsdp else None,
                          expert_parallel=plan.expert_parallel,
                          embed_mode=plan.embed_mode)
    ns = lambda spec: jax.tree.map(  # noqa: E731
        lambda sp: NamedSharding(mesh, sp), spec,
        is_leaf=lambda x: isinstance(x, P))
    tok_spec = S.token_specs(mesh, b)
    specs = input_specs(cfg, shape, plan)

    if sh["kind"] == "train":
        opt_shape = jax.eval_shape(
            partial(adamw.init_state,
                    cfg=adamw.AdamWConfig(state_dtype=plan.opt_state_dtype)),
            pshape)
        opt_spec = {"m": pspec, "v": pspec,
                    "step": P()}
        step = make_train_step(cfg, plan)
        args = (pshape, opt_shape, specs["tokens"])
        in_sh = (ns(pspec), ns(opt_spec), ns(tok_spec))
        if cfg.enc_dec:
            frame_spec = P(S.batch_spec(mesh, b) or None, None, None)
            args += (specs["enc_frames"],)
            in_sh += (ns(frame_spec),)
        out_sh = (ns(pspec), ns(opt_spec),
                  NamedSharding(mesh, P()), NamedSharding(mesh, P()))
        if cfg.n_experts:
            out_sh += (NamedSharding(mesh, P()),)
        return step, args, in_sh, out_sh

    if sh["kind"] == "prefill":
        step = make_prefill_step(cfg, plan, sh["seq"], b)
        cache_shape = jax.eval_shape(
            partial(T.init_cache, cfg, b, sh["seq"],
                    dtype=plan.param_dtype))
        cspec = S.cache_specs(cfg, cache_shape, mesh, b)
        args = (pshape, specs["tokens"])
        in_sh = (ns(pspec), ns(tok_spec))
        logits_spec = S.sanitize(
            P(S.batch_spec(mesh, b) or None, None, "model"),
            (b, 1, cfg.vocab), mesh)
        outs = [NamedSharding(mesh, logits_spec), ns(cspec)]
        if cfg.enc_dec:
            frame_spec = P(S.batch_spec(mesh, b) or None, None, None)
            args += (specs["enc_frames"],)
            in_sh += (ns(frame_spec),)
            outs.append(NamedSharding(mesh, frame_spec))
        return step, args, in_sh, tuple(outs)

    # decode
    step = make_decode_step(cfg, plan)
    cspec = S.cache_specs(cfg, specs["cache"], mesh, b)
    args = (pshape, specs["tokens"], specs["cache"], specs["pos"])
    in_sh = (ns(pspec), ns(tok_spec), ns(cspec),
             NamedSharding(mesh, P()))
    logits_spec = S.sanitize(
        P(S.batch_spec(mesh, b) or None, None, "model"),
        (b, 1, cfg.vocab), mesh)
    out_sh = (ns(tok_spec), NamedSharding(mesh, logits_spec), ns(cspec))
    if cfg.enc_dec:
        frame_spec = P(S.batch_spec(mesh, b) or None, None, None)
        args += (specs["memory"],)
        in_sh += (ns(frame_spec),)
    return step, args, in_sh, out_sh
