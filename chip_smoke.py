"""Chip smoke: SLOTH's main path once on one TPU, through its entry points.

    python chip_smoke.py

One process, phases in order; any failure exits nonzero and no phase
catches and carries on.

1. ``device`` — JAX must report a TPU.  Otherwise the script exits
   before any work and prints no result.
2. ``detect`` — ``Sloth.detect`` with the on-device batched recorder on
   ``resnet50`` with a 10x core fail-slow and on healthy ``darknet19``
   (4x4 mesh).  The positive verdict matches its failure; each verdict's
   top-1, ``flagged`` and sketch bytes equal the numpy oracle's
   (``recorder_impl="ref"``); the recorder's state sat on the TPU.
3. ``pod`` — the live ``PodDetector`` at its deployed geometry (16x16
   chips, 32-step windows, d=2 m=1024 H=4 L=2048) over ``PodSimulator``
   windows, one healthy and then two with one chip 10x slow.  The slow
   chip is flagged, and the batched verdicts equal the ref impl's
   window for window.
4. ``train`` — ``repro.launch.train.main`` on full-width SmolLM-135M (30
   layers, d_model 576, vocab 49152), 24 steps of batch 8 x seq 512 with
   ``--telemetry`` and an injected 10x slow window that must be flagged.
   Every loss is finite and the last is below the first.
5. ``serve`` — ``repro.launch.serve.main`` on full-width SmolLM-135M, 8
   requests of 16 new tokens with ``--telemetry``; every request comes
   back with its tokens.

Each phase prints one JSON line: compile seconds (tracing, lowering and
backend compile), wall seconds, the device's peak bytes in use so far,
and its verdicts.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

TRAIN_ARGV = ["--arch", "smollm-135m", "--steps", "24", "--batch", "8",
              "--seq", "512", "--telemetry", "--telemetry-window", "8",
              "--inject-slow-at", "10", "--inject-slow-steps", "6",
              "--inject-slow-factor", "10", "--expect-flagged"]
SERVE_ARGV = ["--arch", "smollm-135m", "--requests", "8", "--max-new", "16",
              "--telemetry"]
POD_WINDOWS = 3          # one healthy window, then the slow chip
POD_SLOW_CHIP = 7 * 16 + 14

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def require_tpu():
    """The first device, which must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    return dev


def detect_phase() -> dict:
    from repro.core.failures import FailSlow
    from repro.core.graph import build_workload
    from repro.core.routing import Mesh2D
    from repro.core.sloth import Sloth, SlothConfig

    failure = FailSlow(kind="core", location=6, t0=1.0, duration=8.0,
                       slowdown=10.0)
    out = {}
    for workload, failures in (("resnet50", [failure]), ("darknet19", [])):
        v = {impl: Sloth(build_workload(workload), Mesh2D(4),
                         cfg=SlothConfig(recorder_impl=impl))
             .detect(failures, seed=0)
             for impl in ("batched", "ref")}
        b, r = v["batched"], v["ref"]
        top1 = {impl: [c[:2] for c in x.ranking[:1]]
                for impl, x in v.items()}
        require(b.recorder.device == "tpu",
                f"{workload}: recorder state on {b.recorder.device!r}")
        require(top1["batched"] == top1["ref"] and b.flagged == r.flagged
                and b.recorder.sketch_bytes == r.recorder.sketch_bytes,
                f"{workload}: batched top-1 {top1['batched']} "
                f"flagged={b.flagged} bytes={b.recorder.sketch_bytes} != "
                f"ref {top1['ref']} flagged={r.flagged} "
                f"bytes={r.recorder.sketch_bytes}")
        if failures:
            require(b.matches(failures[0]),
                    f"{workload}: verdict {b.kind} {b.location} misses "
                    f"the injected core {failure.location}")
        out[workload] = {"flagged": b.flagged, "top1": top1["batched"],
                         "sketch_bytes": b.recorder.sketch_bytes,
                         "recorder_device": b.recorder.device}
    return out


def pod_phase() -> dict:
    from repro.core.failures import FailSlow
    from repro.distributed.telemetry import (PodDetector, PodSimulator,
                                             PodTelemetryConfig)

    cfgs = {impl: PodTelemetryConfig(recorder_impl=impl)
            for impl in ("batched", "ref")}
    dets = {impl: PodDetector(c) for impl, c in cfgs.items()}
    cfg = cfgs["batched"]
    pod = PodSimulator(cfg, step_flops=8e11, collective_bytes=128e9, seed=0)
    t, verdicts = 0.0, []
    for w in range(POD_WINDOWS):
        if w == 1:
            pod.inject(FailSlow("core", POD_SLOW_CHIP, t, 1e9, 10.0))
        window = pod.run_steps(cfg.window_steps, t0=t,
                               step0=w * cfg.window_steps)
        t += float(window.total_time)
        vb, vr = (dets[impl].observe(window) for impl in ("batched", "ref"))
        # severity is a z-score over f32 (batched) vs f64 (ref) statistics
        require((vb.flagged, vb.kind, vb.location, vb.action)
                == (vr.flagged, vr.kind, vr.location, vr.action)
                and math.isclose(vb.severity, vr.severity, rel_tol=1e-4),
                f"pod window {w}: batched {vb} != ref {vr}")
        verdicts.append({"flagged": vb.flagged, "kind": vb.kind,
                         "location": vb.location, "action": vb.action,
                         "severity": vb.severity})
    last = verdicts[-1]
    require(last["flagged"] and last["kind"] == "core"
            and last["location"] == POD_SLOW_CHIP,
            f"pod: slow chip {POD_SLOW_CHIP} not flagged: {last}")
    return {"mesh": f"{cfg.mesh_w}x{cfg.mesh_h}",
            "window_steps": cfg.window_steps, "verdicts": verdicts}


def train_phase() -> dict:
    from repro.launch import train

    losses = train.main(TRAIN_ARGV)
    require(len(losses) > 1 and all(math.isfinite(x) for x in losses),
            f"train: losses not all finite: {losses}")
    require(losses[-1] < losses[0],
            f"train: loss did not fall ({losses[0]} -> {losses[-1]})")
    return {"argv": " ".join(TRAIN_ARGV), "steps": len(losses),
            "loss_first": losses[0], "loss_last": losses[-1]}


def serve_phase() -> dict:
    from repro.launch import serve

    done = serve.main(SERVE_ARGV)
    n_req = int(SERVE_ARGV[SERVE_ARGV.index("--requests") + 1])
    max_new = int(SERVE_ARGV[SERVE_ARGV.index("--max-new") + 1])
    require(len(done) == n_req
            and all(len(r.out_tokens) == max_new for r in done),
            f"serve: {len(done)} of {n_req} requests, token counts "
            f"{[len(r.out_tokens) for r in done]}")
    return {"argv": " ".join(SERVE_ARGV), "requests": len(done),
            "tokens": sum(len(r.out_tokens) for r in done)}


PHASES = (("detect", detect_phase), ("pod", pod_phase),
          ("train", train_phase), ("serve", serve_phase))


def main() -> None:
    dev = require_tpu()
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(json.dumps({"phase": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(jax.devices()),
                      "compile_cache": cache}), flush=True)
    for name, fn in PHASES:
        c0 = _compile_s[0]
        t0 = time.perf_counter()
        info = fn()
        wall = time.perf_counter() - t0
        stats = dev.memory_stats() or {}
        print(json.dumps({"phase": name, "wall_s": wall,
                          "compile_s": _compile_s[0] - c0,
                          "peak_bytes": stats.get("peak_bytes_in_use"),
                          **info}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
