"""Model assembly for all assigned architectures.

Layers are stacked into *periods*: the layer pattern of an architecture
repeats with period = lcm(attn_period, moe_period) (jamba: 8 = one attention
+ seven mamba layers, MoE on every other layer; dense/MoE archs: 1).  The
forward pass is a ``lax.scan`` over periods whose body applies the period's
slots in order — this keeps the lowered HLO small (one period body) and
makes per-layer FSDP gathering natural.  Leading dense layers
(``first_dense``, DeepSeek-V3's ``first_k_dense_replace``) are a ``lead``
stack scanned the same way before the periods.

Three entry points:
  * ``forward_train``  — full-sequence causal forward (no cache) → logits
  * ``prefill``        — fills a KV/SSM cache, returns last-token logits
  * ``decode_step``    — one token with cache (rolling buffer for SWA)

Whisper (enc_dec) runs its encoder over stub frame embeddings and gives the
decoder per-layer cross-attention; its frontend conv stack is a stub by
assignment.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from . import mamba2 as M
from . import policy


# ---------------------------------------------------------------------------
# period structure
# ---------------------------------------------------------------------------

def period_len(cfg) -> int:
    a = cfg.attn_period if cfg.attn_period else 1
    m = cfg.moe_period if cfg.n_experts else 1
    return math.lcm(a, m)


def period_slots(cfg) -> list[tuple[str, bool]]:
    """[(kind, is_moe)] for one period."""
    return [(cfg.layer_kind(i), cfg.is_moe_layer(i))
            for i in range(cfg.first_dense, cfg.first_dense + period_len(cfg))]


#: the slots of the ``lead`` stack: one dense attention layer each
LEAD_SLOTS = [("attn", False)]


def n_periods(cfg) -> int:
    pl, n = period_len(cfg), cfg.n_layers - cfg.first_dense
    if n % pl:
        raise ValueError(f"{cfg.name}: {n} layers after the leading dense "
                         f"ones are not whole periods of {pl}")
    return n // pl


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_mla(cfg, rng, dtype):
    """Latent attention in its training form: direct queries, the joint
    down projection to the latent and the shared rotary key, the latent's
    norm, its up projection to per-head keys and values, the output."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope = cfg.head_dim - cfg.qk_rope_dim
    hv = h * cfg.v_head_dim
    k = jax.random.split(rng, 4)
    normal = lambda key, shape, fan_in: (  # noqa: E731
        jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)
    return {
        "wq": normal(k[0], (d, h * cfg.head_dim), d),
        "wkv_a": normal(k[1], (d, r + cfg.qk_rope_dim), d),
        "kv_norm": L.init_norm(cfg, r),
        "wkv_b": normal(k[2], (r, h * (nope + cfg.v_head_dim)), r),
        "wo": normal(k[3], (hv, d), hv),
    }


def _init_attn(cfg, rng, dtype, cross: bool = False):
    if cfg.mla:
        return _init_mla(cfg, rng, dtype)
    d = cfg.d_model
    hq = cfg.n_heads * cfg.head_dim
    hk = cfg.n_kv_heads * cfg.head_dim
    k = jax.random.split(rng, 4)
    std = d ** -0.5
    p = {
        "wq": (jax.random.normal(k[0], (d, hq)) * std).astype(dtype),
        "wk": (jax.random.normal(k[1], (d, hk)) * std).astype(dtype),
        "wv": (jax.random.normal(k[2], (d, hk)) * std).astype(dtype),
        "wo": (jax.random.normal(k[3], (hq, d)) * (hq ** -0.5)).astype(dtype),
    }
    return p


def _init_slot(cfg, rng, kind, is_moe, dtype):
    ks = jax.random.split(rng, 6)
    p = {"norm1": L.init_norm(cfg, cfg.d_model)}
    if kind == "attn":
        p["attn"] = _init_attn(cfg, ks[0], dtype)
        if cfg.enc_dec:
            p["xnorm"] = L.init_norm(cfg, cfg.d_model)
            p["xattn"] = _init_attn(cfg, ks[1], dtype, cross=True)
    else:
        p["mamba"] = M.init_mamba(cfg, ks[0], dtype)
    if cfg.d_ff > 0:
        p["norm2"] = L.init_norm(cfg, cfg.d_model)
        if is_moe:
            p["moe"] = L.init_moe(cfg, ks[2], dtype)
        else:
            p["mlp"] = L.init_mlp(cfg, ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_model(cfg, rng, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 8)
    np_, slots = n_periods(cfg), period_slots(cfg)
    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab)) * 0.02).astype(dtype)

    def init_period(prng):
        sk = jax.random.split(prng, len(slots))
        return {f"slot{i}": _init_slot(cfg, sk[i], kind, moe, dtype)
                for i, (kind, moe) in enumerate(slots)}

    period_keys = jax.random.split(ks[2], np_)
    params["periods"] = jax.vmap(init_period)(period_keys)
    if cfg.first_dense:
        params["lead"] = jax.vmap(lambda k: {"slot0": _init_slot(
            cfg, k, *LEAD_SLOTS[0], dtype)})(
                jax.random.split(ks[4], cfg.first_dense))

    if cfg.enc_dec:
        ek = jax.random.split(ks[3], cfg.n_enc_layers + 1)

        def init_enc(prng):
            kk = jax.random.split(prng, 3)
            return {
                "norm1": L.init_norm(cfg, cfg.d_model),
                "attn": _init_attn(cfg, kk[0], dtype),
                "norm2": L.init_norm(cfg, cfg.d_model),
                "mlp": L.init_mlp(cfg, kk[1], cfg.d_model, cfg.d_ff, dtype),
            }
        params["encoder"] = jax.vmap(init_enc)(ek[:-1])
        params["enc_final_norm"] = L.init_norm(cfg, cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def _sinusoid(positions, d):
    """Sinusoidal position embedding (whisper-style, table-free)."""
    half = d // 2
    freq = jnp.exp(-jnp.arange(half) * (np.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _mla_qkv(cfg, p, x, positions):
    """Per-head q, k (head_dim = nope + rope) and v (v_head_dim) of latent
    attention: k and v come up from the normed latent; the rotary part of
    k is one head that all heads share."""
    b, s, _ = x.shape
    h, r, rope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    nope = cfg.head_dim - rope
    q = (x @ p["wq"]).reshape(b, s, h, cfg.head_dim)
    ckv = x @ p["wkv_a"]
    c = L.rms_norm(ckv[..., :r], p["kv_norm"]["scale"], cfg.rms_eps)
    kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + cfg.v_head_dim)
    q_pe = L.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = L.apply_rope(ckv[:, :, None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], axis=-1)
    return q, k, kv[..., nope:]


def _attn_sublayer(cfg, p, x, q_pos, k_pos, *, kv=None, cache=None,
                   causal=True):
    """Self/cross attention.  kv: source for K/V (cross-attn memory)."""
    b, s, d = x.shape
    positions = jnp.broadcast_to(q_pos[None], (b, s))
    if cfg.mla:
        q, k, v = _mla_qkv(cfg, p, x, positions)
    else:
        src = kv if kv is not None else x
        q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (src @ p["wk"]).reshape(b, src.shape[1], cfg.n_kv_heads,
                                    cfg.head_dim)
        v = (src @ p["wv"]).reshape(b, src.shape[1], cfg.n_kv_heads,
                                    cfg.head_dim)
        if kv is None:
            q, k = L.position_embed(cfg, q, k, positions)
    if cache is not None:
        kc, vc, pc = cache.update(k, v, q_pos)
        if s == 1:
            # decode: attend over the cache contents
            k, v, k_pos = kc, vc, pc
        else:
            # prefill: attend over the full in-flight K/V (the rolling
            # buffer only receives the tail); k_pos = q_pos
            k_pos = q_pos
    window = cfg.window if kv is None else None
    out = L.attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                      causal=causal and kv is None, window=window)
    return out.reshape(b, s, cfg.n_heads * cfg.v_head_dim) @ p["wo"]


@dataclasses.dataclass
class _KVView:
    """Rolling KV cache view for one attention slot."""
    k: jax.Array          # [B, T, Hkv, Dh]
    v: jax.Array
    pos_tab: jax.Array    # [T] absolute positions (-1 = empty)
    pos: jax.Array        # scalar: tokens seen so far
    new: tuple = ()

    def update(self, k_new, v_new, q_pos):
        t_max = self.k.shape[1]
        s = k_new.shape[1]
        if s >= t_max:
            # prefill larger than the buffer: keep the last t_max tokens,
            # laid out at their rolling slots (idx = pos % t_max) so later
            # decode writes overwrite the *oldest* entry
            tail_pos = q_pos[-t_max:]
            idx = tail_pos % t_max
            k = self.k.at[:, idx].set(k_new[:, -t_max:]
                                      .astype(self.k.dtype))
            v = self.v.at[:, idx].set(v_new[:, -t_max:]
                                      .astype(self.v.dtype))
            pos_tab = self.pos_tab.at[idx].set(tail_pos)
            self.new = (k, v, pos_tab)
            return k, v, pos_tab
        idx = (self.pos + jnp.arange(s)) % t_max
        k = self.k.at[:, idx].set(k_new.astype(self.k.dtype))
        v = self.v.at[:, idx].set(v_new.astype(self.v.dtype))
        pos_tab = self.pos_tab.at[idx].set(q_pos)
        self.new = (k, v, pos_tab)
        return k, v, pos_tab


def _apply_slot(cfg, p, kind, is_moe, x, q_pos, *, memory=None,
                slot_cache=None, dtype=None):
    """One layer: mixer + (cross-attn) + MLP/MoE with residuals.
    Returns (x, aux_loss, expert_load or None, new_slot_cache)."""
    aux = jnp.zeros((), jnp.float32)
    load = new_cache = None
    h = L.apply_norm(cfg, x, p["norm1"])
    if kind == "attn":
        scope = (jax.named_scope("mla") if cfg.mla
                 else contextlib.nullcontext())
        with scope:
            if slot_cache is not None:
                view = _KVView(slot_cache["k"], slot_cache["v"],
                               slot_cache["pos_tab"], slot_cache["pos"])
                out = _attn_sublayer(cfg, p["attn"], h, q_pos, None,
                                     cache=view)
                new_cache = {"k": view.new[0], "v": view.new[1],
                             "pos_tab": view.new[2],
                             "pos": slot_cache["pos"] + h.shape[1]}
            else:
                out = _attn_sublayer(cfg, p["attn"], h, q_pos, q_pos)
        x = x + out
        if cfg.enc_dec and memory is not None:
            hx = L.apply_norm(cfg, x, p["xnorm"])
            x = x + _attn_sublayer(
                cfg, p["xattn"], hx, q_pos,
                jnp.arange(memory.shape[1]), kv=memory, causal=False)
    else:
        state = None
        if slot_cache is not None:
            state = (slot_cache["conv"], slot_cache["ssm"])
        out, new_state = M.mamba_block(cfg, p["mamba"], h, state)
        x = x + out
        if slot_cache is not None:
            new_cache = {"conv": new_state[0],
                         "ssm": new_state[1].astype(slot_cache["ssm"].dtype),
                         "pos": slot_cache["pos"] + h.shape[1]}
    if cfg.d_ff > 0:
        h2 = L.apply_norm(cfg, x, p["norm2"])
        if is_moe:
            out2, aux, load = L.moe(cfg, p["moe"], h2)
        else:
            out2 = L.mlp(cfg, p["mlp"], h2)
        x = x + out2
    return x, aux, load, new_cache


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------

def run_encoder(cfg, params, frames):
    """frames [B, F, d]: precomputed frontend-stub embeddings."""
    x = frames + _sinusoid(jnp.arange(frames.shape[1]),
                           cfg.d_model)[None].astype(frames.dtype)
    pos = jnp.arange(frames.shape[1])

    def body(h, p):
        a = L.apply_norm(cfg, h, p["norm1"])
        h = h + _attn_sublayer(cfg, p["attn"], a, pos, pos, causal=False)
        m = L.apply_norm(cfg, h, p["norm2"])
        h = h + L.mlp(cfg, p["mlp"], m)
        return h, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["encoder"])
    return L.apply_norm(cfg, x, params["enc_final_norm"])


# ---------------------------------------------------------------------------
# main forward paths
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens, positions):
    x = params["embed"][tokens]
    if cfg.pos == "learned":
        x = x + _sinusoid(positions, cfg.d_model)[None].astype(x.dtype)
    return x


def _logits(cfg, params, x):
    x = L.apply_norm(cfg, x, params["final_norm"])
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def _scan_stack(cfg, stack, slots, x, q_pos, memory, caches, remat):
    """Scan a stack of layers (leading axis) whose body applies ``slots``.
    Returns (x, aux_loss, expert loads [n, moe slots, held] or None,
    new caches)."""
    def body(carry, xs):
        h, aux = carry
        pp = xs if caches is None else xs[0]
        cc = None if caches is None else xs[1]
        h = policy.constrain_residual(h)
        new_cc, loads = {}, []
        for i, (kind, moe) in enumerate(slots):
            sc = None if cc is None else cc[f"slot{i}"]
            h, a, load, nc = _apply_slot(cfg, pp[f"slot{i}"], kind, moe, h,
                                         q_pos, memory=memory,
                                         slot_cache=sc)
            aux = aux + a
            if load is not None:
                loads.append(load)
            if nc is not None:
                if "ssm" in nc:
                    nc["ssm"] = policy.constrain_state(nc["ssm"])
                new_cc[f"slot{i}"] = nc
        h = policy.constrain_residual(h)
        return (h, aux), (new_cc if caches is not None else None,
                          jnp.stack(loads) if loads else None)

    fn = jax.checkpoint(body,
                        policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    xs = stack if caches is None else (stack, caches)
    (x, aux), (new_caches, loads) = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, loads, new_caches


def _scan_periods(cfg, params, x, q_pos, memory=None, caches=None,
                  remat=True):
    """The ``lead`` stack, then the periods.  Returns (x, aux_loss, expert
    load [MoE layers, held] or None, new caches)."""
    new_lead = None
    if cfg.first_dense:
        lead_c = None if caches is None else caches["lead"]
        x, _, _, new_lead = _scan_stack(cfg, params["lead"], LEAD_SLOTS, x,
                                        q_pos, memory, lead_c, remat)
        if caches is not None:
            caches = {k: c for k, c in caches.items() if k != "lead"}
    x, aux, loads, new_caches = _scan_stack(
        cfg, params["periods"], period_slots(cfg), x, q_pos, memory, caches,
        remat)
    if new_lead is not None:
        new_caches["lead"] = new_lead
    if loads is not None:
        loads = loads.reshape(-1, loads.shape[-1])
    return x, aux, loads, new_caches


def forward_train(cfg, params, tokens, enc_frames=None, remat=True):
    """tokens [B,S] → (logits [B,S,V], aux_loss, expert load): the load is
    the token-expert pairs each held expert computed, [MoE layers, held],
    or None without experts."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = _embed(cfg, params, tokens, positions)
    memory = None
    if cfg.enc_dec:
        memory = run_encoder(cfg, params, enc_frames)
    x, aux, load, _ = _scan_periods(cfg, params, x, positions, memory,
                                    remat=remat)
    return _logits(cfg, params, x), aux, load


# -- cache construction -------------------------------------------------------

def init_cache(cfg, batch, cache_len, dtype=jnp.bfloat16,
               memory_len: int = 0):
    """Cache pytree with leading period axis (scan xs/ys layout); with
    leading dense layers, their cache under ``lead``.  Latent attention
    keeps per-head keys and values (the expanded form)."""
    per = _stack_cache(cfg, n_periods(cfg), period_slots(cfg), batch,
                       cache_len, dtype)
    if cfg.first_dense:
        per["lead"] = _stack_cache(cfg, cfg.first_dense, LEAD_SLOTS, batch,
                                   cache_len, dtype)
    return per


def _stack_cache(cfg, np_, slots, batch, cache_len, dtype):
    t_max = cache_len if cfg.window is None else min(cache_len,
                                                     cfg.window)
    per = {}
    for i, (kind, _) in enumerate(slots):
        if kind == "attn":
            per[f"slot{i}"] = {
                "k": jnp.zeros((np_, batch, t_max, cfg.n_kv_heads,
                                cfg.head_dim), dtype),
                "v": jnp.zeros((np_, batch, t_max, cfg.n_kv_heads,
                                cfg.v_head_dim), dtype),
                "pos_tab": jnp.full((np_, t_max), -1, jnp.int32),
                "pos": jnp.zeros((np_,), jnp.int32),
            }
        else:
            conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            per[f"slot{i}"] = {
                "conv": jnp.zeros((np_, batch, cfg.ssm_conv - 1, conv_dim),
                                  dtype),
                "ssm": jnp.zeros((np_, batch, cfg.ssm_heads,
                                  cfg.ssm_head_dim, cfg.ssm_state),
                                 jnp.float32),
                "pos": jnp.zeros((np_,), jnp.int32),
            }
    return per


def prefill(cfg, params, tokens, cache, enc_frames=None, remat=True):
    """Run S prompt tokens, filling ``cache``.  Returns (last_logits, cache,
    memory) — memory is the encoder output for enc-dec archs."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = _embed(cfg, params, tokens, positions)
    memory = None
    if cfg.enc_dec:
        memory = run_encoder(cfg, params, enc_frames)
    x, _, _, new_caches = _scan_periods(cfg, params, x, positions, memory,
                                        caches=cache, remat=remat)
    return _logits(cfg, params, x[:, -1:]), new_caches, memory


def decode_step(cfg, params, tokens, cache, pos, memory=None):
    """One decode step.  tokens [B,1]; pos: scalar int32 absolute position."""
    positions = jnp.full((1,), pos, jnp.int32)
    x = _embed(cfg, params, tokens, positions)
    x, _, _, new_caches = _scan_periods(cfg, params, x, positions, memory,
                                        caches=cache, remat=False)
    return _logits(cfg, params, x), new_caches
