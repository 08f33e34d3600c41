"""Batched serving runtime: prefill + decode loop over a request batch.

Single-host reference implementation of the serve path the dry-run lowers
at pod scale: uniform-batch prefill, greedy decode with the rolling KV /
SSM cache, simple admission queue.

Per-step timings are recorded in **separate** ``prefill_times`` /
``decode_times`` series: prefill steps are O(prompt·seq) and decode
steps O(1)-ish, so mixing them in one series would inflate every decode
percentile computed downstream.  An optional ``step_hook(kind, dt)``
callback fires after each step (``kind`` is ``'prefill'`` or
``'decode'``) — the live telemetry tap ``launch/serve.py --telemetry``
uses to stream decode timings into the pod detector
(:class:`~repro.distributed.telemetry.StepTelemetry`).  Each timing ends
with ``block_until_ready`` on the step's next tokens, so it covers the
device step and not only its dispatch; it is read on
:func:`repro.core.spans.clock`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spans import clock
from ..models import transformer as T


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [S] int32
    max_new: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineConfig:
    batch: int = 4
    cache_len: int = 512
    dtype: object = jnp.float32


def _greedy(out):
    """``(logits, *rest)`` → ``(next tokens [B, 1] int32, *rest)``."""
    nxt = jnp.argmax(out[0][:, -1], axis=-1)[:, None].astype(jnp.int32)
    return (nxt,) + tuple(out[1:])


class ServeEngine:
    def __init__(self, cfg, params, ecfg: EngineConfig, step_hook=None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.queue: list[Request] = []
        self.prefill_times: list[float] = []
        self.decode_times: list[float] = []
        self.step_hook = step_hook             # fn(kind, dt) | None

        # both steps return the greedy next tokens [B, 1] in place of
        # the logits: (next, cache[, memory])
        self._prefill = jax.jit(
            lambda p, toks, frames=None: _greedy(T.prefill(
                cfg, p, toks,
                T.init_cache(cfg, ecfg.batch, ecfg.cache_len,
                             dtype=ecfg.dtype),
                enc_frames=frames, remat=False)))
        self._decode = jax.jit(
            lambda p, toks, cache, pos, memory=None: _greedy(T.decode_step(
                cfg, p, toks, cache, pos, memory=memory)))

    def submit(self, req: Request):
        self.queue.append(req)

    def _record(self, kind: str, dt: float) -> None:
        (self.prefill_times if kind == "prefill"
         else self.decode_times).append(dt)
        if self.step_hook is not None:
            self.step_hook(kind, dt)

    def _next_batch(self) -> list[Request]:
        batch = self.queue[:self.ecfg.batch]
        self.queue = self.queue[self.ecfg.batch:]
        return batch

    def run(self, enc_frames=None) -> list[Request]:
        """Serve everything in the queue; returns completed requests."""
        done: list[Request] = []
        while self.queue:
            batch = self._next_batch()
            # pad the batch to engine batch size (replicate last request)
            while len(batch) < self.ecfg.batch:
                batch.append(Request(-1, batch[-1].prompt, 0))
            s = max(len(r.prompt) for r in batch)
            toks = np.stack([np.pad(r.prompt, (s - len(r.prompt), 0))
                             for r in batch]).astype(np.int32)
            t0 = clock()
            args = (self.params, toks) + ((enc_frames,) if self.cfg.enc_dec
                                          else ())
            out = self._prefill(*args)
            nxt, cache = out[0], out[1]
            memory = out[2] if self.cfg.enc_dec else None
            nxt.block_until_ready()     # time the device step, not the enqueue
            self._record("prefill", clock() - t0)
            max_new = max(r.max_new for r in batch)
            for k in range(max_new):
                for r, t in zip(batch, np.asarray(nxt)[:, 0]):
                    if r.rid >= 0 and len(r.out_tokens) < r.max_new:
                        r.out_tokens.append(int(t))
                t0 = clock()
                nxt, cache = self._decode(self.params, nxt, cache,
                                          jnp.int32(s + k), memory)
                nxt.block_until_ready()
                self._record("decode", clock() - t0)
            done.extend(r for r in batch if r.rid >= 0)
        return done
