"""Public wrapper: GQA-aware flash attention.

Accepts model-layout tensors (q [B,S,Hq,D], k/v [B,T,Hkv,D]), broadcasts KV
heads to query heads, flattens (batch, head) and dispatches to the Pallas
kernel or the jnp oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref


def gqa_attention(q, k, v, *, causal=True, window=None, impl="pallas",
                  interpret=None, q_block=128, kv_block=128):
    b, s, hq, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    g = hq // hk
    kx = jnp.repeat(k, g, axis=2)
    vx = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = kx.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    vf = vx.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    if impl == "pallas":
        of = flash_attention(qf, kf, vf, causal=causal, window=window,
                             q_block=q_block, kv_block=kv_block,
                             interpret=interpret)
    else:
        of = attention_ref(qf, kf, vf, causal=causal, window=window)
    return of.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
