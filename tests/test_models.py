"""Per-architecture smoke tests (reduced configs, one fwd/train step on
CPU) + KV-cache equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, list_archs
from repro.models import layers as L
from repro.models import transformer as T

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

ARCHS = list_archs()


def _inputs(cfg, rng, b=2, s=24):
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab)
    kw = {}
    if cfg.enc_dec:
        kw["enc_frames"] = jax.random.normal(
            rng, (b, cfg.n_frames, cfg.d_model)) * 0.02
    return tokens, kw


def test_all_archs_registered():
    assert len(ARCHS) == 12


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    rng = jax.random.PRNGKey(0)
    params = T.init_model(cfg, rng, dtype=jnp.float32)
    tokens, kw = _inputs(cfg, rng)
    logits, aux, _ = T.forward_train(cfg, params, tokens, **kw)
    assert logits.shape == (2, 24, cfg.vocab)
    assert not np.any(np.isnan(np.asarray(logits)))
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ["smollm-135m", "mixtral-8x7b",
                                  "mamba2-1.3b", "jamba-1.5-large-398b",
                                  "whisper-large-v3",
                                  "moonlight-16b-a3b-ep8"])
def test_smoke_train_step(arch):
    from repro.launch.steps import CellPlan, make_train_step
    from repro.optim import adamw
    cfg = get_config(arch, smoke=True)
    rng = jax.random.PRNGKey(0)
    params = T.init_model(cfg, rng, dtype=jnp.float32)
    opt_cfg = adamw.AdamWConfig()
    opt = adamw.init_state(params, opt_cfg)
    step = jax.jit(make_train_step(
        cfg, CellPlan(grad_accum=1, remat=False,
                      param_dtype=jnp.float32), opt_cfg))
    tokens, kw = _inputs(cfg, rng)
    args = (params, opt, tokens) + ((kw["enc_frames"],) if cfg.enc_dec
                                    else ())
    p2, o2, loss, gnorm, *load = step(*args)
    assert np.isfinite(float(loss)) and float(loss) > 0
    assert np.isfinite(float(gnorm))
    # MoE steps also return each held expert's token-expert pairs per
    # MoE layer; a dropless layer computes every pair routed to it
    assert bool(load) == bool(cfg.n_experts)
    if load and not cfg.capacity_factor:
        n_moe = sum(map(cfg.is_moe_layer, range(cfg.n_layers)))
        assert load[0].shape == (n_moe, cfg.experts_held)
        assert int(load[0].sum()) <= n_moe * tokens.size * cfg.top_k
    # params actually moved
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), params, p2))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_equivalence(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.n_experts:   # avoid capacity-truncation mismatches
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    rng = jax.random.PRNGKey(0)
    params = T.init_model(cfg, rng, dtype=jnp.float32)
    tokens, kw = _inputs(cfg, rng)
    logits, _, _ = T.forward_train(cfg, params, tokens, **kw)
    cache = T.init_cache(cfg, 2, 64, dtype=jnp.float32)
    last, cache, mem = T.prefill(cfg, params, tokens, cache, **kw)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)
    nxt = jax.random.randint(jax.random.PRNGKey(7), (2, 1), 0, cfg.vocab)
    dec, _ = T.decode_step(cfg, params, nxt, cache, jnp.int32(24),
                           memory=mem)
    full, _, _ = T.forward_train(cfg, params,
                              jnp.concatenate([tokens, nxt], 1), **kw)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_masks_history():
    """SWA: logits must be invariant to tokens beyond the window."""
    cfg = dataclasses.replace(get_config("h2o-danube-3-4b", smoke=True),
                              window=8)
    rng = jax.random.PRNGKey(0)
    params = T.init_model(cfg, rng, dtype=jnp.float32)
    t1 = jax.random.randint(rng, (1, 32), 0, cfg.vocab)
    t2 = t1.at[:, :8].set((t1[:, :8] + 7) % cfg.vocab)  # mutate old tokens
    l1, _, _ = T.forward_train(cfg, params, t1)
    l2, _, _ = T.forward_train(cfg, params, t2)
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               rtol=1e-5, atol=1e-5)


def test_param_counts_match_claims():
    expect = {"yi-34b": 34e9, "mixtral-8x7b": 46.7e9, "dbrx-132b": 132e9,
              "jamba-1.5-large-398b": 398e9, "smollm-135m": 135e6,
              "moonlight-16b-a3b": 16e9}
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.05, (arch, got, n)
    # Moonlight-16B-A3B: 3B active; one chip's share under 8-way expert
    # parallelism, the dense layer and 5 MoE layers: 83 M + 5 x 100.4 M
    # + 84 M of vocabulary
    moon = get_config("moonlight-16b-a3b")
    assert abs(moon.active_param_count() - 3e9) / 3e9 < 0.05
    share = get_config("moonlight-16b-a3b-ep8")
    assert share.param_count() == 668_888_384
    params = jax.eval_shape(lambda: T.init_model(
        share, jax.random.PRNGKey(0), dtype=jnp.float32))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == share.param_count() + share.d_model   # and the final norm


# ---------------------------------------------------------------------------
# Moonlight-16B-A3B: latent attention, held experts, dropless routing
# ---------------------------------------------------------------------------

def _moon_reference():
    import sys
    from pathlib import Path
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from reference import moonlight
    return moonlight


#: a small MoE layer with the published routing: 64 experts, 6 a token
SMALL_MOE = dict(d_model=32, d_expert=16, n_experts=64, top_k=6,
                 n_shared_experts=2)


def _small_moe(held=64, offset=0, **kw):
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b", smoke=True), **SMALL_MOE,
        experts_held=held, expert_offset=offset, **kw)
    params = L.init_moe(cfg, jax.random.PRNGKey(3), jnp.float32)
    params["router_bias"] = jax.random.normal(jax.random.PRNGKey(4),
                                              (64,)) * 0.01
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32))
    return cfg, params, x


def _reference_moe(cfg, params, x, held=64, offset=0):
    """The reference's MoE layer over ``held`` experts from ``offset``."""
    m = {"num_experts_per_tok": cfg.top_k, "n_routed_experts": held,
         "expert_offset": offset, "routed_scaling_factor": cfg.router_scale}
    with jax.default_matmul_precision("highest"):
        y = _moon_reference().moe(m, params, x.reshape(-1, x.shape[-1]))
    return np.asarray(y).reshape(x.shape)


def _share(params, offset, held):
    return {k: (v[offset:offset + held] if k.startswith("w_") else v)
            for k, v in params.items()}


def test_expert_shares_add_up_to_the_whole_layer():
    """8 chips each holding 8 of the 64 experts: their outputs, with the
    shared experts every chip computes counted once, add up to the uncut
    layer of the reference."""
    cfg, params, x = _small_moe()
    total = 0
    with jax.default_matmul_precision("highest"):
        for i in range(8):
            share = dataclasses.replace(cfg, experts_held=8,
                                        expert_offset=8 * i)
            y, _, load = L.moe(share, _share(params, 8 * i, 8), x)
            total = total + np.asarray(y)
            assert load.shape == (8,)
        shared = np.asarray(L.mlp(cfg, params["shared"], x))
    np.testing.assert_allclose(total - 7 * shared,
                               _reference_moe(cfg, params, x),
                               rtol=1e-5, atol=1e-5)


def test_a_held_expert_takes_every_token_and_drops_none():
    """The selection bias forced so that every token picks held expert 3
    and five experts held elsewhere: the dropless layer computes all 96
    tokens on expert 3 and still equals the reference, where a capacity
    of 1.25x the mean load would drop most of them."""
    cfg, params, x = _small_moe()
    bias = jnp.zeros((64,)).at[3].set(10.0).at[40:45].set(5.0)
    params = dict(params, router_bias=bias)
    share = dataclasses.replace(cfg, experts_held=8, expert_offset=0)
    mine = _share(params, 0, 8)
    want = _reference_moe(cfg, mine, x, held=8)
    with jax.default_matmul_precision("highest"):
        y, _, load = L.moe(share, mine, x)
        capped, _, capped_load = L.moe(
            dataclasses.replace(share, capacity_factor=1.25), mine, x)
    assert load.tolist() == [0, 0, 0, 96, 0, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    assert int(capped_load[3]) < 96
    assert not np.allclose(np.asarray(capped), want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("s,window", [(24, None), (4096, None), (4096, 512)])
def test_attention_output_takes_the_value_width(s, window):
    """q and k of 24 per head, v of 16: the direct, chunked and banded
    paths give [B, S, H, 16] and agree."""
    from repro.models import layers as Lm
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, s, 2, 24))
    k = jax.random.normal(ks[1], (1, s, 2, 24))
    v = jax.random.normal(ks[2], (1, s, 2, 16))
    pos = jnp.arange(s)
    out = Lm.attention(q, k, v, q_pos=pos, k_pos=pos, window=window)
    assert out.shape == (1, s, 2, 16)
    want = Lm._direct_attention(q, k, v, pos, pos, True, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_latent_attention_is_causal_and_matches_the_reference():
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    p = T._init_mla(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    pos = jnp.arange(16)
    with jax.default_matmul_precision("highest"):
        q, k, v = T._mla_qkv(cfg, p, x, jnp.broadcast_to(pos, (2, 16)))
        out = T._attn_sublayer(cfg, p, x, pos, pos)
        later = T._attn_sublayer(cfg, p, x.at[:, 10:].set(0.5), pos, pos)
        m = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
             "kv_lora_rank": cfg.kv_lora_rank,
             "qk_nope_head_dim": cfg.head_dim - cfg.qk_rope_dim,
             "qk_rope_head_dim": cfg.qk_rope_dim,
             "v_head_dim": cfg.v_head_dim, "vocab_size": cfg.vocab,
             "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta}
        want = _moon_reference().attention(m, p, x)
    assert q.shape == k.shape == (2, 16, cfg.n_heads, cfg.head_dim)
    assert v.shape == (2, 16, cfg.n_heads, cfg.v_head_dim) \
        and cfg.v_head_dim != cfg.head_dim
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(later[:, :10]),
                                  np.asarray(out[:, :10]))
    assert not np.allclose(np.asarray(later[:, 10:]), np.asarray(out[:, 10:]))


#: the smoke SmolLM's loss and gradient norms by leaf, recorded before
#: latent attention, held experts and the leading dense stack came in
SMOLLM_LOSS = 5.536121368408203
SMOLLM_GRAD_NORMS = [
    2.596385955810547, 0.01852402836084366, 0.1575183868408203,
    0.24524343013763428, 0.17046555876731873, 0.24927496910095215,
    0.2049432396888733, 0.14000195264816284, 0.1320926994085312,
    0.044605109840631485, 0.02710762433707714]


def test_smollm_is_unchanged():
    """Output width from v, the config's norm epsilon, aux weight and
    ``lead`` stack all default to what SmolLM computed before them.  The
    tolerance is float32 summation order on another CPU."""
    from repro.launch import steps
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    (loss, (_, load)), grads = jax.value_and_grad(
        lambda p: steps.loss_fn(cfg, p, tokens, remat=False),
        has_aux=True)(params)
    assert load is None and "lead" not in params
    np.testing.assert_allclose(float(loss), SMOLLM_LOSS, rtol=1e-6)
    np.testing.assert_allclose(
        [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)],
        SMOLLM_GRAD_NORMS, rtol=1e-5)


def test_lm_graph_refuses_latent_attention():
    """``build_lm_graph`` sizes attention from ``head_dim``: a latent
    attention config is an error, not a graph of wrong sizes."""
    from repro.core.graph import build_lm_graph
    assert build_lm_graph(get_config("mixtral-8x7b", smoke=True),
                          seq=16, batch=1).nodes
    with pytest.raises(ValueError, match="latent attention"):
        build_lm_graph(get_config("moonlight-16b-a3b", smoke=True))
