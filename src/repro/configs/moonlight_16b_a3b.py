"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, DeepSeek-V3
architecture]: multi-head latent attention, 64 fine-grained experts
(6 per token, sigmoid router, noaux_tc top-k, weights normalised and
scaled by 2.446) beside 2 shared experts, after one leading dense layer.

``moonlight-16b-a3b-ep8`` is what one chip holds when 8 chips share each
MoE layer by expert parallelism and further pipeline stages hold the
other layers: experts 0-7 of every layer's 64 (the router keeps its 64
outputs), an eighth of the vocabulary, all of attention, the shared
experts, the dense layer and 5 of the 26 MoE layers.  Every width is the
published one.
"""
import dataclasses

from .base import ArchConfig, register

_WIDTHS = dict(
    d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264, rms_eps=1e-5,
    head_dim=192, qk_rope_dim=64, v_head_dim=128, kv_lora_rank=512,
    n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2,
    capacity_factor=0.0, router_score="sigmoid", router_scale=2.446,
    aux_weight=0.0, first_dense=1, rope_theta=5e4, mlp="swiglu",
    norm="rms", family="moe")


def full() -> ArchConfig:
    return ArchConfig(name="moonlight-16b-a3b", n_layers=27, vocab=163840,
                      **_WIDTHS)


def ep8() -> ArchConfig:
    return ArchConfig(name="moonlight-16b-a3b-ep8", n_layers=6, vocab=20480,
                      experts_held=8, expert_offset=0, **_WIDTHS)


def smoke() -> ArchConfig:
    return ArchConfig(
        name="moonlight-16b-a3b-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab=256, rms_eps=1e-5, head_dim=24,
        qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, n_experts=16,
        top_k=4, d_expert=32, n_shared_experts=2, capacity_factor=0.0,
        router_score="sigmoid", router_scale=2.446, aux_weight=0.0,
        first_dense=1, rope_theta=5e4, mlp="swiglu", norm="rms",
        family="moe")


def ep8_smoke() -> ArchConfig:
    """The share at the smoke size: experts 0-1 of 16 (an eighth)."""
    return dataclasses.replace(smoke(), name="moonlight-16b-a3b-ep8-smoke",
                               experts_held=2, expert_offset=0)


register("moonlight-16b-a3b", full, smoke)
register("moonlight-16b-a3b-ep8", ep8, ep8_smoke)
