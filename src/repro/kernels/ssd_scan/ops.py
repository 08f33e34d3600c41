"""Public wrapper for the SSD scan kernel (model-layout adapters)."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import ssd_scan
from .ref import ssd_ref


def ssd(x, dt, a, b, c, *, impl: str = "pallas", chunk: int = 128,
        interpret: bool | None = None):
    """Model layout: x [B,S,H,P], dt [B,S,H], a [H], b/c [B,S,G,N] →
    (y [B,S,H,P], state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    bh = jnp.repeat(b, rep, axis=2)
    ch = jnp.repeat(c, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    af = jnp.tile(a, bsz)
    bf = bh.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    cf = ch.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    fn = ssd_scan if impl == "pallas" else ssd_ref
    if impl == "pallas":
        y, st = fn(xf, dtf, af, bf, cf, chunk=chunk, interpret=interpret)
    else:
        y, st = fn(xf, dtf, af, bf, cf)
    return (y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3),
            st.reshape(bsz, h, p, n))
