"""Driver for training configurations (``"driver": "train"``).

The system under test is the program's launcher, ``repro.launch.train.
main``, run with the configuration's arguments (and ``--telemetry`` where
the traffic asks for it).  The harness does not copy its loop.  It wraps
three attributes around it:

* ``repro.models.transformer.init_model`` returns the benchmark's weights,
  made from the seed on the device in one jitted call by the cell's
  reference, in the layout the program's own init gives;
* ``TokenPipeline.__next__`` hands out the benchmark's tokens from the
  seed and takes each step's start time; ``setup_steps`` steps in, it
  opens the window, and at the first step start past the window's end it
  stops the loop;
* the launcher's ``jax.jit`` wraps the compiled step, so that the first
  gradient (from AdamW's first moment before step 1) and the weights'
  change after three steps (before step 3 runs) are read off the very
  object that the window then drives.

``train_tokens_per_s`` is the tokens of the steps started in the window
over the window's length; ``train_step_p95_ms`` the 95th percentile of
the time between successive step starts, so a step that carries a
telemetry flush counts with it.  Correctness compares the first three
steps' losses, the first gradient and the change after three steps with
the reference, by leaf slice, and, with telemetry, a sample of the
telemetry verdicts drawn from the seed with the reference pipeline over
the same windows, layer by layer (recorder patterns, core scores, link
estimates, FailRank where reached, the verdict).

The configuration names its plain reference with ``"reference":
"<name>"``, ``bench/reference/<name>.py`` (``Cell.reference``); the
driver and ``train_mfu`` reach the model only through it, so a model
joins by new files.  A training reference provides:

* ``_Frozen(model)``, a hashable view of the configuration's ``model``
  group, and ``init_params(frozen, key)``, the seed's weights in the
  program's layout, traceable under ``jax.jit``;
* ``make_params_from_seed(model, seed)``, the same weights made on the
  device in one jitted call;
* ``leaf_norms(tree)``, the norm of every leaf slice in leaf order;
* ``batch(seed, step, rows, seq, vocab)``, the tokens of one step;
* ``run_reference(model, opt, seed, rows, seq, steps=3, block=2,
  dtype=float32, use_rows=None)``, the losses, the first clipped
  gradient's and the three steps' change's norms by leaf slice;
* ``train_flops_per_step(model, batch, seq)``, the model FLOPs of one
  step; without it ``train_mfu`` is left out.

The Llama reference, ``"reference": "lm"``, provides all of them; its
generic parts (the token stream, AdamW, ``leaf_norms``, ``_Frozen``) are
there for a later reference to import, not to copy.
"""

from __future__ import annotations

import copy
import functools
import gc
import sys
import time
import types
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import (BENCH_DIR, Outcome, Reservoir, load_module,  # noqa: E402
                     percentile)
from reference import sloth as pod_ref  # noqa: E402

pod = load_module(BENCH_DIR / "drivers" / "pod.py", "bench_pod_compare")


class WindowClosed(Exception):
    """Raised at the first step start past the window's end."""


def argv_for(config: dict, traffic: dict, seed: int) -> list[str]:
    o = config["train"]["optimizer"]
    argv = ["--arch", config["arch"], "--steps", str(o["total_steps"]),
            "--batch", str(traffic["batch"]), "--seq", str(traffic["seq"]),
            "--lr", repr(o["lr"]), "--seed", str(seed),
            "--log-every", str(o["total_steps"] + 1)]
    argv += config.get("launcher_args", [])
    if traffic["telemetry"]:
        argv += ["--telemetry", "--telemetry-window",
                 str(config["telemetry"]["window_steps"])]
    return argv


@functools.partial(jax.jit, static_argnums=0)
def _first_grad_norms(ref, first_moment, b1):
    """The first (clipped) gradient by leaf slice, from AdamW's first
    moment after one step."""
    return ref.leaf_norms(first_moment) / (1 - b1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _change_norms(ref, frozen, params, key):
    """The weights' change since the seed's weights, by leaf slice.  The
    key is an argument, so every seed runs the same compiled program."""
    start = ref.init_params(frozen, key)
    return ref.leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))


class StepProbe:
    """The launcher's compiled step, with readings taken off its state:
    before call 1 the first (clipped) gradient from AdamW's first moment,
    before call 3 the weights' change since the seed's weights."""

    def __init__(self, fn, ref, model: dict, seed: int, b1: float, rec):
        self.fn = fn
        self.ref = ref
        self.rec = rec
        self.calls = 0
        self.losses = []
        self.grad_norms = self.delta_norms = None
        self.b1 = b1
        self.key = jax.random.PRNGKey(seed)
        self.frozen = ref._Frozen(model)

    def __call__(self, params, opt_state, tokens, *rest):
        i = self.calls
        self.calls += 1
        if i == 1:
            self.grad_norms = np.asarray(_first_grad_norms(
                self.ref, opt_state["m"], self.b1))
        if i == 3:
            self.delta_norms = np.asarray(_change_norms(
                self.ref, self.frozen, params, self.key))
        with self.rec.span("train_step"):
            out = self.fn(params, opt_state, tokens, *rest)
        self.losses.append(out[2])
        return out


def gap(got, want) -> float:
    """Widest gap by leaf between two norms, as a share of the reference
    leaf's norm or of the median leaf's, whichever is larger."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)
                        / np.maximum(want, np.median(want))))


def compare_steps(probe_losses, grad_norms, delta_norms, want) -> dict:
    """The training numbers: the first three losses, the first gradient
    and the change after three steps, against the reference's ``want``.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change."""
    lr = np.asarray(want["losses"])
    keep = want["grad_norms"] >= 1e-3 * np.median(want["grad_norms"])
    return {"loss_gap": float(np.max(np.abs(np.asarray(probe_losses) - lr)
                                     / np.abs(lr))),
            "grad_gap": gap(grad_norms, want["grad_norms"]),
            "update_gap": gap(np.asarray(delta_norms)[keep],
                              want["delta_norms"][keep])}


class Loop:
    """The state the wrapped attributes share during one ``main`` call."""

    def __init__(self, ctx, setup_steps: int):
        self.ctx = ctx
        self.ref = ctx.cell.reference
        self.setup_steps = setup_steps
        self.starts: list[float] = []
        self.step = 0
        self.t_open = self.t_close = None
        self.window = None

    def next_batch(self, pipe):
        now = time.perf_counter()
        if self.step == self.setup_steps:
            self.window = self.ctx.window()
            self.window.__enter__()
            now = self.t_open = time.perf_counter()
        elif self.t_open is not None and now >= self.t_open \
                + self.ctx.seconds:
            self.t_close = now
            raise WindowClosed
        if self.t_open is not None:
            self.starts.append(now)
        c = pipe.cfg
        out = self.ref.batch(self.ctx.seed, self.step, c.batch, c.seq,
                             c.vocab)
        self.step += 1
        return out


def run(ctx) -> Outcome:
    from repro.data import pipeline
    from repro.distributed import telemetry as tel
    from repro.launch import train
    from repro.models import transformer as T

    cfg, tr, rec = ctx.config, ctx.traffic, ctx.rec
    model, opt = cfg["model"], cfg["train"]["optimizer"]
    loop = Loop(ctx, tr["setup_steps"])
    ref = loop.ref
    probe = {}

    def init_model(arch_cfg, rng, dtype=None):
        want = jax.eval_shape(lambda: orig_init(arch_cfg, rng, dtype=dtype))
        got = ref.make_params_from_seed(model, ctx.seed)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                                   jax.tree.leaves(got))):
            raise RuntimeError("the program's weight layout is not the "
                               "reference's")
        return got

    def jit(fn, **kw):
        probe["step"] = StepProbe(jax.jit(fn, **kw), ref, model, ctx.seed,
                                  opt["b1"], rec)
        return probe["step"]

    orig_init = T.init_model
    for owner, attr, new in (
            (T, "init_model", init_model),
            (pipeline.TokenPipeline, "__next__",
             lambda pipe: loop.next_batch(pipe)),
            (train, "jax", _JaxWith(jit))):
        rec.replace(owner, attr, new)
    seen = TelemetrySeen(cfg["telemetry"], ctx.seed,
                         tr.get("compare_windows", 0))
    if tr["telemetry"]:
        seen.instrument(rec, tel)
    try:
        train.main(argv_for(cfg, tr, ctx.seed))
        raise RuntimeError("the launcher ran out of steps before the "
                           "window closed")
    except WindowClosed:
        pass
    finally:
        rec.unwrap_all()
        if loop.window is not None:
            loop.window.__exit__(None, None, None)
    step = probe.pop("step")
    n = len(loop.starts)
    t_open, t_close = loop.t_open, loop.t_close
    periods = np.diff(np.asarray(loop.starts + [t_close]))
    compiles = rec.compiles_within(t_open, t_close)
    account_for_stalls(loop.starts, periods, rec, compiles)
    losses = np.asarray([float(x) for x in step.losses])
    failed = int(np.sum(~np.isfinite(losses[tr["setup_steps"]:])))
    first = losses[:3].tolist()
    grad_norms, delta_norms = step.grad_norms, step.delta_norms
    del step, probe
    gc.collect()

    want = ref.run_reference(model, opt, ctx.seed, tr["batch"], tr["seq"])
    nums = compare_steps(first, grad_norms, delta_norms, want)
    if tr["telemetry"]:
        nums.update(seen.numbers())
    checks = [ctx.cell.check(k, v) for k, v in nums.items()]
    b, s = tr["batch"], tr["seq"]
    window_s = t_close - t_open
    return Outcome(
        end_to_end={"train_tokens_per_s": n * b * s / window_s,
                    "train_step_p95_ms": 1e3 * percentile(periods, 95),
                    "setup_s": t_open - ctx.t_start},
        checks=checks, attempted=n, failed=failed,
        window=(t_open, t_close),
        extra={"reference": want, "telemetry": seen,
               "counters": {"steps": n, "window_s": window_s,
                            "compiles": compiles}})


def account_for_stalls(starts, periods, rec, compiles: int) -> None:
    """Where the window's longest gaps between step starts went, printed
    to standard error and read by no metric: the tokens and their
    transfer before the step's call, the call (dispatch), and what follows
    it up to the next step's start (the wait for the loss, the telemetry
    flush, the launcher's own work); and the compiles inside the
    window."""
    def inside(name, t0, t1):
        return [(a, b) for a, b in rec.spans.get(name, ()) if t0 <= a < t1]

    parts = []
    for i in np.argsort(periods)[::-1][:3]:
        t0, t1 = starts[i], starts[i] + periods[i]
        call = inside("train_step", t0, t1)
        flush = sum(b - a for a, b in inside("telemetry", t0, t1))
        if call:
            a, b = call[0]
            parts.append(f"{1e3 * periods[i]:.1f} ms at step {i} (before "
                         f"the call {1e3 * (a - t0):.1f}, call "
                         f"{1e3 * (b - a):.1f}, after {1e3 * (t1 - b):.1f} "
                         f"of which flush {1e3 * flush:.1f})")
    print(f"bench: longest step periods: {'; '.join(parts)}; median "
          f"{1e3 * float(np.median(periods)):.1f} ms; compiles and cache "
          f"loads in the window: {compiles}", file=sys.stderr)


class TelemetrySeen:
    """What the program's telemetry verdicts were, kept for the
    comparison: every window handed to ``PodDetector.observe`` (the
    reference rebuilds the cumulative stream from them), and the program's
    layer readings (``bench/drivers/pod.py``) of the last verdict and of
    ``compare - 1`` earlier ones drawn from the seed."""

    def __init__(self, tcfg: dict, seed: int, compare: int):
        self.tcfg = tcfg
        self.windows: list = []
        self.earlier = Reservoir(max(compare - 1, 0),
                                 np.random.default_rng([seed, 3]))
        self.last = None
        self._cur: dict = {}

    def instrument(self, rec, tel) -> None:
        self._cur = pod.instrument(rec)
        rec.wrap(tel.StepTelemetry, "flush", "telemetry")
        rec.wrap(tel.PodDetector, "observe", "pod_observe")
        rec.hooks["pod_observe"] = self._on_observe

    def _on_observe(self, args, kw, v) -> None:
        cur, n_cores = self._cur, self.tcfg["mesh_w"] * self.tcfg["mesh_h"]
        item = (len(self.windows), pod.program_reading(
            cur, (v.flagged, v.kind, v.location), n_cores), cur["elapsed"])
        cur.clear()
        self.windows.append(args[1])
        if self.last is not None:
            self.earlier.offer(self.last)
        self.last = item

    @property
    def sample(self) -> list:
        return self.earlier.items + (
            [] if self.last is None else [self.last])

    def numbers(self, control: bool = False) -> dict:
        """The sampled verdicts of the program, or with ``control`` of the
        reference in bfloat16 put in its place, against the reference on
        the same windows: the widest gap of each layer (``pod.compare``)
        and the verdicts that differ."""
        t = self.tcfg
        at = {i: (got, elapsed) for i, got, elapsed in self.sample}
        mesh = pod_ref.Mesh(t["mesh_w"], t["mesh_h"])
        ref = pod_ref.Stream(t["sketch"])
        low = pod_ref.Stream(t["sketch"], lowp=True) if control else None
        nums = {"telemetry_verdicts_off": 0}
        for i, w in enumerate(self.windows[:max(at, default=-1) + 1]):
            for stream in (ref, low) if control else (ref,):
                stream.observe(w.comp, w.comm)
            if i not in at:
                continue
            got, elapsed = at[i]
            want = pod_ref.verdict(copy.deepcopy(ref), mesh,
                                   t["detect_threshold"])
            if control:
                got = pod_ref.verdict(copy.deepcopy(low), mesh,
                                      t["detect_threshold"], lowp=True)
            nums["telemetry_verdicts_off"] += got.verdict != want.verdict
            for k, x in pod.compare(got, want, elapsed).items():
                key = "telemetry_" + k
                nums[key] = max(nums.get(key, 0.0), x)
        return nums


class _JaxWith(types.ModuleType):
    """The ``jax`` module as the launcher sees it, with ``jit`` replaced."""

    def __init__(self, jit):
        super().__init__("jax")
        self.jit = jit

    def __getattr__(self, name):
        return getattr(jax, name)


def calibrate(cell, seed: int) -> dict:
    """Readings that set the limits, with no measured window.  Without
    telemetry: the launcher's first three steps against the reference
    (``program``); the reference in bfloat16 against it (``control``); and
    the reference with half of each batch left out (``half_batch``, a
    fault read in the reference put in the program's place).  With
    telemetry: ``calibrate_seconds`` of monitored steps, every telemetry
    verdict of them compared (``program``), and the pod reference in
    bfloat16 in the program's place (``control``)."""
    import jax.numpy as jnp
    from harness import Recorder

    class _NoWindow:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    tr = cell.traffic
    watch = bool(tr["telemetry"])
    ctx = types.SimpleNamespace(
        cell=cell, config=cell.config, seed=seed,
        seconds=float(tr.get("calibrate_seconds", 0.0)) if watch else 0.0,
        traffic=dict(tr, setup_steps=4, compare_windows=10 ** 6),
        t_start=time.perf_counter(), rec=Recorder(), window=_NoWindow)
    out = run(ctx)
    readings = {"program": {c.name: c.value for c in out.checks}}
    if watch:
        seen = out.extra["telemetry"]
        readings["control"] = seen.numbers(control=True)
        print(f"bench: seed {seed}: {len(seen.sample)} telemetry verdicts "
              "compared", file=sys.stderr)
        return readings
    model, opt = cell.config["model"], cell.config["train"]["optimizer"]
    rows, seq = tr["batch"], tr["seq"]
    want = out.extra["reference"]
    for side, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"use_rows": rows // 2})):
        r = cell.reference.run_reference(model, opt, seed, rows, seq, **kw)
        readings[side] = compare_steps(r["losses"], r["grad_norms"],
                                       r["delta_norms"], want)
    return readings
