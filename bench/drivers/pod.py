"""Driver for pod detector configurations (``"driver": "pod"``).

The system under test is the program's ``PodDetector.observe``: one
telemetry window in, one verdict out.  The traffic file gives the
telemetry's statistics; the generator here draws windows from the seed
with the statistical model of the program's ``PodSimulator`` (chip step
time normal with a per-chip speed, ICI transfer gamma, a failure scaling
one chip's or one link's time), vectorised.  Drawing them is set-up and is
not timed: the windows stand in for real chips.

Set-up observes ``history_windows`` healthy windows, snapshots the
detector and makes one warm verdict.  In the window every verdict
restores the snapshot and observes the next fresh window, so every
verdict does the same work at the same history depth.  ``verdict_s`` is
the mean time of ``observe`` over the verdicts started in the window.

Correctness: every verdict is held to the injected truth, and a sample
of verdicts drawn from the seed is compared, layer by layer, with the
plain reference (``bench/reference/sloth.py``) on the same windows.
"""

from __future__ import annotations

import copy
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import Outcome, Reservoir  # noqa: E402
from reference import sloth as ref  # noqa: E402


# ---------------------------------------------------------------------------
# traffic: telemetry windows from the seed
# ---------------------------------------------------------------------------

class Windows:
    """Telemetry windows of a ``mesh_w x mesh_h`` pod, drawn from the seed.

    ``window(i, failure)`` is window ``i`` of the stream: its steps
    continue the absolute step count after ``start_step`` steps and its
    clock starts at ``t0``.  Each window has a stream of its own
    (``[seed, i]``), so the same seed gives the same windows.
    """

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.tr = traffic
        self.mesh = ref.Mesh(config["mesh_w"], config["mesh_h"])
        self.links = np.array(sorted(self.mesh.ids, key=self.mesh.ids.get),
                              dtype=np.int64)
        self.n_steps = config["window_steps"]
        self.seed = seed
        rng = np.random.default_rng([seed, 0])
        self.speed = 1.0 + traffic["chip_speed_sd"] * rng.standard_normal(
            self.mesh.n_cores)

    def window(self, i: int, step0: int, t0: float, failure: dict | None):
        from repro.core.simulator import SimResult
        tr, nc, n = self.tr, self.mesh.n_cores, self.n_steps
        nl = len(self.links)
        rng = np.random.default_rng([self.seed, 1 + i])
        base = tr["step_flops"] / tr["chip_flops_per_s"]
        jit = 1.0 + tr["jitter"] * np.abs(rng.standard_normal((n, nc)))
        durs = base * jit / self.speed[None, :]
        k = tr["gamma_shape"]
        per_link = tr["collective_bytes"] / nl
        svc_slow = np.ones(nl)
        if failure and failure["kind"] == "core":
            durs[:, failure["location"]] *= failure["slowdown"]
        if failure and failure["kind"] == "link":
            svc_slow[failure["location"]] = failure["slowdown"]
        svc = (per_link * rng.gamma(k, 1.0 / k, (n, nl)) * svc_slow
               / tr["link_bytes_per_s"] + 1e-6)
        ends = np.cumsum(durs.max(axis=1) + svc.max(axis=1))
        starts = t0 + np.concatenate([[0.0], ends[:-1]])
        steps = step0 + np.arange(n, dtype=np.int64)
        stage = steps // tr["stage_steps"]
        t_start = np.repeat(starts, nc)
        comp = {"core": np.tile(np.arange(nc, dtype=np.int64), n),
                "node": np.repeat(steps, nc),
                "part": np.zeros(n * nc, dtype=np.int64),
                "stage": np.repeat(stage, nc),
                "op": np.ones(n * nc, dtype=np.int64),
                "flops": np.full(n * nc, float(tr["step_flops"])),
                "t_start": t_start, "t_end": t_start + durs.ravel()}
        u, v = self.links[:, 0], self.links[:, 1]
        depart = (starts[:, None] + durs[:, u]).ravel()
        comm = {"src": np.tile(u, n), "dst": np.tile(v, n),
                "stage": np.repeat(stage, nl),
                "bytes": np.full(n * nl, per_link),
                "t_depart": depart, "t_arrive": depart + svc.ravel(),
                "hops": np.ones(n * nl, dtype=np.int64),
                "service": svc.ravel()}
        return SimResult(total_time=float(ends[-1]), comp=comp, comm=comm,
                         n_raw_records=n * (nc + nl))


def make_traffic(config: dict, traffic: dict, seed: int):
    """``(history, fresh)``: the healthy windows observed in set-up, and
    the windows the verdicts observe, each continuing the history."""
    gen = Windows(config, traffic, seed)
    n = config["window_steps"]
    history, t = [], 0.0
    for i in range(traffic["history_windows"]):
        w = gen.window(i, i * n, t, None)
        history.append(w)
        t += w.total_time
    step0 = len(history) * n
    fresh = [gen.window(len(history) + j, step0, t, traffic["failure"])
             for j in range(traffic["windows"])]
    return history, fresh


def truth(traffic: dict) -> tuple:
    f = traffic["failure"]
    return (False, None, None) if f is None else (True, f["kind"],
                                                  f["location"])


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def pattern_gap(prog, refp, elapsed: float) -> float:
    """Widest relative gap between the program's and the reference's
    patterns; 1.0 where keys, counts or arrival order differ."""
    P = {p.key: p for p in prog}
    R = {p.key: p for p in refp}
    if P.keys() != R.keys():
        return 1.0
    worst = 0.0
    for key, r in R.items():
        p = P[key]
        if p.count != r.count or p.arrival != r.arrival:
            return 1.0
        for a in ("sum_dur", "sum_sq_dur", "sum_val", "min_dur"):
            x, y = getattr(p, a), getattr(r, a)
            worst = max(worst, abs(x - y) / max(abs(y), 1e-300))
        for a in ("t_first", "t_last"):
            worst = max(worst, abs(getattr(p, a) - getattr(r, a))
                        / max(elapsed, 1e-300))
    return worst


def program_reading(cap: dict, v: tuple, n_cores: int) -> ref.Reading:
    """What the program's layers returned for one verdict (``cap``, filled
    by the hooks of ``instrument``), as a reference ``Reading``."""
    rec = cap["rec"]
    z = np.zeros(n_cores)
    for c in cap["cores"]:
        z[c.core] = max(z[c.core], c.z)
    fr = cap.get("fr")
    return ref.Reading(
        comp=rec.comp_patterns, comm=rec.comm_patterns,
        sketch_bytes=rec.sketch_bytes, core_z=z,
        theta=np.asarray(cap["links"].theta),
        observed=np.asarray(cap["links"].observed),
        failrank=None if fr is None else np.asarray(fr.raw_node_scores,
                                                    dtype=np.float64),
        verdict=v, severity=0.0)


def compare(got: ref.Reading, want: ref.Reading, elapsed: float) -> dict:
    """The numbers of one verdict: ``got`` against the reference's
    ``want``, layer by layer."""
    out = {"pattern_gap": max(
        0.0 if got.sketch_bytes == want.sketch_bytes else 1.0,
        pattern_gap(got.comp, want.comp, elapsed),
        pattern_gap(got.comm, want.comm, elapsed))}
    out["z_gap"] = float(np.max(np.abs(got.core_z - want.core_z)
                                / np.maximum(np.abs(want.core_z), 1.0)))
    if not np.array_equal(got.observed, want.observed):
        out["theta_gap"] = 1.0
    else:
        m = want.observed
        out["theta_gap"] = float(np.max(
            np.abs(got.theta[m] - want.theta[m])
            / np.maximum(np.abs(want.theta[m]), 1e-300), initial=0.0))
    if got.failrank is not None or want.failrank is not None:
        if got.failrank is None or want.failrank is None:
            out["failrank_gap"] = 1.0
        else:
            out["failrank_gap"] = float(
                np.max(np.abs(got.failrank - want.failrank))
                / max(np.max(np.abs(want.failrank)), 1e-12))
    return out


def reference_readings(config: dict, history, windows, lowp=False):
    """The reference's reading of each window in ``windows``, each
    observed after the healthy ``history``."""
    mesh = ref.Mesh(config["mesh_w"], config["mesh_h"])
    base = ref.Stream(config["sketch"], lowp=lowp)
    for h in history:
        base.observe(h.comp, h.comm)
    out = []
    for w in windows:
        s = copy.deepcopy(base)
        s.observe(w.comp, w.comm)
        out.append(ref.verdict(s, mesh, config["detect_threshold"],
                               lowp=lowp))
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def pod_config(config: dict):
    from repro.core.sketch import SketchParams
    from repro.distributed.telemetry import PodTelemetryConfig
    return PodTelemetryConfig(
        mesh_w=config["mesh_w"], mesh_h=config["mesh_h"],
        topology=config["topology"], window_steps=config["window_steps"],
        sketch=SketchParams(**config["sketch"]),
        detect_threshold=config["detect_threshold"],
        recorder_impl=config["recorder_impl"])


def instrument(rec) -> dict:
    """Spans around the verdict's layers; returns the dict that the
    hooks fill with what the current verdict's layers returned."""
    from repro.core import streaming
    from repro.distributed import telemetry as tel
    cur: dict = {}
    rec.wrap(streaming.StreamingRecorder, "observe", "record")
    rec.wrap(streaming.StreamingRecorder, "output", "record")
    for name in ("detect_cores", "detect_links"):
        rec.wrap(tel, name, "detect")
    for name in ("build_mcg", "failrank", "attribute_links"):
        rec.wrap(tel, name, "failrank")

    def on_record(args, kw, out):
        if out is not None:
            cur["rec"] = out
            cur["elapsed"] = args[0].elapsed

    def on_detect(args, kw, out):
        cur["links" if hasattr(out, "theta") else "cores"] = out

    def on_failrank(args, kw, out):
        if hasattr(out, "raw_node_scores"):
            cur["fr"] = out

    rec.hooks.update(record=on_record, detect=on_detect, failrank=on_failrank)
    return cur


def run(ctx) -> Outcome:
    from repro.distributed.telemetry import PodDetector
    cfg, tr, rec = ctx.config, ctx.traffic, ctx.rec
    history, fresh = make_traffic(cfg, tr, ctx.seed)
    cur = instrument(rec)
    det = PodDetector(pod_config(cfg))
    for h in history:
        det.observe(h)
    snapshot = copy.deepcopy(det)
    copy.deepcopy(snapshot).observe(fresh[0])     # warm every shape
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start

    n_cores = cfg["mesh_w"] * cfg["mesh_h"]
    sample = Reservoir(tr["compare"],
                       np.random.default_rng([ctx.seed, 2 ** 20]))
    times, verdicts = [], []
    with ctx.window():
        t_open = time.perf_counter()
        deadline = t_open + ctx.seconds
        i = 0
        while i == 0 or time.perf_counter() < deadline:
            d = copy.deepcopy(snapshot)
            cur.clear()
            w = i % len(fresh)
            t0 = time.perf_counter()
            v = d.observe(fresh[w])
            times.append(time.perf_counter() - t0)
            verdicts.append((v.flagged, v.kind, v.location))
            sample.offer((w, program_reading(cur, verdicts[-1], n_cores),
                          cur["elapsed"]))
            del d
            i += 1
        t_close = time.perf_counter()
    rec.unwrap_all()
    compiles = rec.compiles_within(t_open, t_close)

    expect = truth(tr)
    off_truth = sum(v != expect for v in verdicts)
    readings = reference_readings(cfg, history,
                                  [fresh[w] for w, _, _ in sample.items])
    nums: dict[str, float] = {}
    off_ref = 0
    for (w, got, elapsed), want in zip(sample.items, readings):
        off_ref += got.verdict != want.verdict
        for k, x in compare(got, want, elapsed).items():
            nums[k] = max(nums.get(k, 0.0), x)
    cell = ctx.cell
    checks = [cell.check("verdicts_off_truth", off_truth),
              cell.check("verdicts_off_reference", off_ref)]
    checks += [cell.check(k, nums[k]) for k in
               ("pattern_gap", "z_gap", "theta_gap", "failrank_gap")
               if k in nums]
    return Outcome(
        end_to_end={"verdict_s": math.fsum(times) / len(times),
                    "setup_s": setup_s},
        checks=checks, attempted=len(verdicts), failed=off_truth,
        window=(t_open, t_close),
        extra={"counters": {"verdicts": len(verdicts),
                            "compiles": compiles}})


def calibrate(cell, seed: int) -> dict:
    """Readings that set the limits: the program's numbers against the
    reference, and the control's (the reference in bfloat16), on the
    first fresh window of ``seed``; no measured window."""
    from harness import Recorder
    from repro.distributed.telemetry import PodDetector
    cfg, tr = cell.config, cell.traffic
    history, fresh = make_traffic(cfg, tr, seed)
    windows = fresh[:1]
    rec = Recorder()
    cur = instrument(rec)
    det = PodDetector(pod_config(cfg))
    for h in history:
        det.observe(h)
    got = []
    for w in windows:
        cur.clear()
        v = copy.deepcopy(det).observe(w)
        got.append((program_reading(cur, (v.flagged, v.kind, v.location),
                                    cfg["mesh_w"] * cfg["mesh_h"]),
                    cur["elapsed"]))
    rec.unwrap_all()
    want = reference_readings(cfg, history, windows)
    lowp = reference_readings(cfg, history, windows, lowp=True)
    out = {"program": {}, "control": {}}
    expect = truth(tr)
    for (g, elapsed), r, c in zip(got, want, lowp):
        for side, x in (("program", g), ("control", c)):
            nums = compare(x, r, elapsed)
            nums["verdicts_off_truth"] = int(x.verdict != expect)
            nums["verdicts_off_reference"] = int(x.verdict != r.verdict)
            for k, val in nums.items():
                out[side][k] = max(out[side].get(k, 0), val)
    return out
