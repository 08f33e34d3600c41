"""Serving launcher: batched prefill+decode with SLOTH telemetry hooks.

Decode percentiles come from the engine's ``decode_times`` series, kept
apart from ``prefill_times``: a batch's prefill is much slower than its
decode steps and would inflate the "decode" p50/p99.

``--telemetry`` taps the engine's per-step hook: decode step timings
stream into the pod detector every window
(:class:`~repro.distributed.telemetry.StepTelemetry`), and each
window's verdict is printed live — a fail-slow host during decode
surfaces as a flagged ``core 0`` verdict while serving continues.

CPU example:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 8 --max-new 8 --telemetry
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs.base import get_config
from ..core import spans
from ..distributed.telemetry import PodTelemetryConfig, StepTelemetry
from ..models import transformer as T
from ..serving.engine import EngineConfig, Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="stream decode step timings into the pod "
                         "detector (one verdict per window)")
    ap.add_argument("--telemetry-window", type=int, default=8,
                    help="decode steps per streaming-detector window")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = T.init_model(cfg, jax.random.PRNGKey(args.seed),
                          dtype=jnp.float32)

    telemetry = hook = None
    if args.telemetry:
        telemetry = StepTelemetry(
            PodTelemetryConfig(mesh_w=4, mesh_h=4,
                               window_steps=args.telemetry_window),
            n_shards=args.batch, warmup=1, seed=args.seed,
            host=jax.process_index())

        def hook(kind, dt):
            if kind != "decode":    # prefills are not per-step samples
                return
            v = telemetry.record_step(dt)
            if v is not None and v.flagged:
                print(f"[telemetry] FLAGGED {v.kind} {v.location} "
                      f"severity {v.severity:.1f} -> "
                      f"{telemetry.plans[-1]['action']}")

    engine = ServeEngine(cfg, params,
                         EngineConfig(batch=args.batch,
                                      cache_len=args.cache_len),
                         step_hook=hook)
    # Fold host identity into the request-stream key (campaign.py
    # style) so multi-host launches don't submit identical workloads.
    rng = np.random.default_rng([args.seed, jax.process_index()])
    enc_frames = None
    if cfg.enc_dec:
        enc_frames = jnp.zeros((args.batch, cfg.n_frames, cfg.d_model),
                               jnp.float32)
    for i in range(args.requests):
        n = int(rng.integers(2, args.prompt_len + 1))
        engine.submit(Request(i, rng.integers(0, cfg.vocab, size=n)
                              .astype(np.int32), max_new=args.max_new))
    t0 = spans.clock()
    done = engine.run(enc_frames=enc_frames)
    wall = spans.clock() - t0
    tok = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {tok} tokens, {wall:.1f}s "
          f"({tok / max(wall, 1e-9):.1f} tok/s)")
    if engine.prefill_times:
        print(f"mean prefill {np.mean(engine.prefill_times) * 1e3:.1f} ms "
              f"({len(engine.prefill_times)} batches)")
    if engine.decode_times:
        print(f"p50 decode step {np.median(engine.decode_times) * 1e3:.1f}"
              f" ms, p99 {np.quantile(engine.decode_times, 0.99) * 1e3:.1f}"
              " ms")
    if telemetry is not None:
        telemetry.flush()
        n_flagged = sum(v.flagged for v in telemetry.verdicts)
        print(f"[telemetry] {len(telemetry.verdicts)} windows, "
              f"{n_flagged} flagged")
    return done


if __name__ == "__main__":
    main()
