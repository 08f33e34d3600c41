"""Training launcher: real training loop with fault tolerance.

Features exercised end-to-end (CPU-scale here, pod-scale by mesh swap):
  * deterministic resumable data pipeline,
  * periodic atomic checkpoints (params + optimizer + data state),
  * crash-resume: ``--resume`` restarts from the latest checkpoint,
  * elastic restart: resuming onto a different mesh re-shards arrays,
  * SLOTH pod telemetry (``--telemetry``): measured per-step wall times
    stream into the pod detector every ``--telemetry-window`` steps
    (:class:`~repro.distributed.telemetry.StepTelemetry`; the local host
    is chip 0); each window's verdict and mitigation plan are logged,
    and an ``exclude_and_restart`` plan triggers an immediate
    checkpoint.  ``--inject-slow-at/--inject-slow-steps/
    --inject-slow-factor`` scale the *reported* timings of a step range
    (training itself is unperturbed) so the detection path is
    demonstrable end-to-end; ``--expect-flagged`` turns "the injected
    slowdown was flagged" into an exit-code assertion (the CI smoke).
  * spans (:mod:`repro.core.spans`): every step is a ``train.step`` span
    with children ``train.data`` (the next batch onto the device),
    ``train.dispatch`` (the jitted step's call), ``train.sync`` (the wait
    for the loss), and ``train.telemetry``, ``train.log``, ``train.ckpt``
    where the step does that work.  The telemetry signal is the step's
    ``dispatch`` start to its ``sync`` end on the one clock.  For MoE
    configs ``train.sync`` also counts ``expert_tokens``, the token-expert
    pairs the held experts computed in the step, and ``expert_load_max``,
    the most that one held expert computed in one layer.
  * ``--remat`` recomputes each layer's activations in the backward pass
    (a model whose activations do not fit beside its weights and AdamW
    state).

Usage (CPU example):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
Telemetry smoke (flags an injected 10x slow window):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 24 --batch 2 --seq 32 --telemetry --telemetry-window 8 \
      --inject-slow-at 10 --inject-slow-steps 6 --expect-flagged
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import store
from ..compile_cache import enable_compile_cache
from ..configs.base import get_config
from ..core import spans
from ..data.pipeline import DataConfig, TokenPipeline
from ..distributed.telemetry import PodTelemetryConfig, StepTelemetry
from ..models import transformer as T
from ..optim import adamw
from . import steps as steps_mod


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="run the SLOTH pod detector on step timings")
    ap.add_argument("--telemetry-window", type=int, default=8,
                    help="steps per streaming-detector window")
    ap.add_argument("--inject-slow-at", type=int, default=None,
                    metavar="STEP", help="scale the telemetry-reported "
                    "timing of this step onward (detection demo; training "
                    "itself is unperturbed)")
    ap.add_argument("--inject-slow-steps", type=int, default=8,
                    help="number of steps the injected slowdown lasts")
    ap.add_argument("--inject-slow-factor", type=float, default=10.0,
                    help="reported-timing multiplier for injected steps")
    ap.add_argument("--expect-flagged", action="store_true",
                    help="exit nonzero unless telemetry flagged a slow "
                    "window (CI smoke assertion)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--remat", action="store_true",
                    help="recompute each layer's activations in the "
                    "backward pass")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps)

    rng = jax.random.PRNGKey(args.seed)
    params = T.init_model(cfg, rng, dtype=jnp.float32)
    opt_state = adamw.init_state(params, opt_cfg)

    data_cfg = DataConfig(vocab=cfg.vocab, batch=args.batch, seq=args.seq,
                          seed=args.seed)
    pipe = TokenPipeline(data_cfg)

    start_step = 0
    if args.resume and args.ckpt_dir:
        latest = store.latest_step(args.ckpt_dir)
        if latest is not None:
            (params, opt_state), extra = store.restore(
                args.ckpt_dir, latest, (params, opt_state))
            pipe = TokenPipeline.restore(data_cfg, extra["data"])
            start_step = latest
            print(f"[resume] step {latest}")

    plan = steps_mod.CellPlan(grad_accum=1, remat=args.remat,
                              param_dtype=jnp.float32)
    train_step = jax.jit(steps_mod.make_train_step(cfg, plan, opt_cfg),
                         donate_argnums=(0, 1))

    telemetry = None
    if args.telemetry:
        tele_cfg = PodTelemetryConfig(mesh_w=4, mesh_h=4,
                                      window_steps=args.telemetry_window)
        telemetry = StepTelemetry(tele_cfg, n_shards=4, warmup=1,
                                  seed=args.seed,
                                  host=jax.process_index())

    enc_frames = None
    if cfg.enc_dec:
        enc_frames = jnp.zeros((args.batch, cfg.n_frames, cfg.d_model),
                               jnp.float32)

    losses = []
    t_begin = spans.clock()
    for step in range(start_step, args.steps):
        with spans.span("train.step", root="step"):
            with spans.span("train.data"):
                tokens = jnp.asarray(next(pipe))
            with spans.span("train.dispatch") as dispatch:
                if cfg.enc_dec:
                    params, opt_state, loss, gnorm, *load = train_step(
                        params, opt_state, tokens, enc_frames)
                else:
                    params, opt_state, loss, gnorm, *load = train_step(
                        params, opt_state, tokens)
            with spans.span("train.sync") as sync:
                loss = float(loss)
                if load:
                    load = np.asarray(load[0])
                    spans.count("expert_tokens", int(load.sum()))
                    spans.count("expert_load_max", int(load.max()))
            losses.append(loss)
            # the telemetry signal: the step's call to its loss on the host
            dt = sync.t1 - dispatch.t0
            if telemetry is not None:
                with spans.span("train.telemetry"):
                    reported = dt
                    if args.inject_slow_at is not None and \
                            args.inject_slow_at <= step \
                            < args.inject_slow_at + args.inject_slow_steps:
                        reported *= args.inject_slow_factor
                    verdict = telemetry.record_step(reported)
                    if verdict is not None:
                        _act_on(verdict, telemetry.plans[-1], step, args,
                                (params, opt_state), pipe, loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                with spans.span("train.log"):
                    print(f"step {step:5d} loss {loss:.4f} gnorm "
                          f"{float(gnorm):.3f} {dt*1e3:.0f} ms")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                with spans.span("train.ckpt"):
                    path = store.save(args.ckpt_dir, step + 1,
                                      (params, opt_state),
                                      extra={"data": pipe.state(),
                                             "loss": loss})
                print(f"[ckpt] {path}")
    wall = spans.clock() - t_begin
    if telemetry is not None:
        telemetry.flush()      # analyse any trailing partial window
        n_flagged = sum(v.flagged for v in telemetry.verdicts)
        print(f"[telemetry] {len(telemetry.verdicts)} windows, "
              f"{n_flagged} flagged")
        if args.expect_flagged and not telemetry.flagged:
            raise SystemExit(
                "telemetry smoke FAILED: no window flagged the injected "
                "slowdown")
    if losses:
        print(f"done: {args.steps - start_step} steps in {wall:.1f}s; "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    else:
        print(f"nothing to do (resumed at {start_step} ≥ {args.steps})")
    return losses


def _act_on(verdict, plan: dict, step: int, args, state, pipe,
            loss: float) -> None:
    """Log a telemetry window's verdict; an ``exclude_and_restart`` plan
    writes a mitigation checkpoint when ``--ckpt-dir`` is set."""
    if not verdict.flagged:
        print(f"[telemetry] step {step}: healthy window")
        return
    print(f"[telemetry] step {step}: FLAGGED {verdict.kind} "
          f"{verdict.location} severity {verdict.severity:.1f} -> "
          f"{plan['action']}")
    if plan.get("exclude_cores") or plan.get("avoid_links"):
        # registry-backed plan (remap/reroute on the pod mesh): the
        # resource edits the restart applies
        print(f"[telemetry] {plan['policy']} plan: exclude cores "
              f"{list(plan.get('exclude_cores', ()))}, avoid links "
              f"{list(plan.get('avoid_links', ()))}")
    if plan["action"] == "exclude_and_restart" and args.ckpt_dir:
        path = store.save(args.ckpt_dir, step + 1, state,
                          extra={"data": pipe.state(), "loss": loss})
        print(f"[telemetry] mitigation checkpoint {path}")


if __name__ == "__main__":
    main()
