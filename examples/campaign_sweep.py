"""Scenario-campaign sweep: reproduce the paper's aggregate metrics.

Runs a grid of fail-slow scenarios (workload × mesh × failure kind ×
severity × n_failures × replicate) through every requested detector
(``--detectors``: SLOTH and/or the five baselines, all judged on the same
traces by the same router-aware rule) and prints per-cell, per-detector
and campaign-level accuracy / FPR / top-k localisation / recall@k /
compression / probe overhead, with Wilson confidence intervals and
wall-time telemetry.

    PYTHONPATH=src python examples/campaign_sweep.py            # full grid
    PYTHONPATH=src python examples/campaign_sweep.py --tiny     # CI smoke
    PYTHONPATH=src python examples/campaign_sweep.py \\
        --tiny --executor process --n-failures 2                # multi-core
    PYTHONPATH=src python examples/campaign_sweep.py \\
        --mesh 12x12 --mesh 16x8 --executor process             # big meshes
    PYTHONPATH=src python examples/campaign_sweep.py \\
        --detectors sloth --detectors thres --detectors adr     # Table III
    PYTHONPATH=src python examples/campaign_sweep.py \\
        --tiny --kinds mixed --kinds none --n-failures 2 \\
        --severities linspace:1.5:3:4 --all-detectors   # mixed + sweep

``--kinds`` accepts the base kinds, ``mixed`` (per-failure kinds sampled
from the live core/link/router population) and ``core+link``-style
composites; ``--severities`` accepts plain slowdown factors and
``linspace:LO:HI:N`` sweep specs.

``--mesh`` / ``--topology`` entries share one fabric-spec grammar,
resolved through the topology registry (``core.routing``)::

    W | WxH            default mesh, e.g. --mesh 12x8
    name:WxH           registered fabric, e.g. --topology torus:8x8
    name:WxH:variant   fabric variant, e.g. --topology het:4x4:fast2slow1

Registered builtins: ``mesh`` (bidirectional 2D mesh, XY routing),
``torus`` (wrap links, shortest-direction DOR), ``systolic``
(unidirectional east/south dataflow with edge re-injection), ``het``
(mesh with a ``fast<A>slow<B>`` rate-class pattern).  Third-party
fabrics join via ``register_topology(name, cls)`` and are then valid in
the same specs.  Campaigns spanning more than one fabric print the
``per fabric`` accuracy/FPR table.  Campaigns with several severities
print the ``severity_curve()`` readout; mixed-kind campaigns print the
per-truth-kind recall split.

``--recorder-impl`` selects the SL-Recorder sketch path: ``ref`` (per-run
numpy oracle, the default), ``batched`` (on-device run-compressed JAX
scan with the drained-eviction stream) or ``both`` — which runs the
campaign once per impl and asserts scenario-for-scenario identical
verdicts and compression ratios (the recorder-parity smoke used in CI).
Compression ratios and pattern structure are integer-derived and always
bit-identical; verdict fields pass through thresholded float scores, and
the batched path keeps Stage-2 statistics in float32 vs the oracle's
float64 — so run ``both`` on decisively-failing grids (the CI smoke's
8× severity), not on near-threshold sweeps where a score within f32
rounding of a flag threshold could legitimately diverge.

``--streaming N`` runs SLOTH incrementally over each trace split into N
chunks (the always-on deployment mode): the campaign gains a
detection-latency column (first flagged chunk's stream time minus the
earliest failure onset), the summary prints the
``detection latency: ...`` aggregate, and — as a gate — a second,
post-hoc campaign is run and every streamed verdict is asserted
scenario-for-scenario identical to its one-shot counterpart (the
streaming-equivalence smoke used in CI).  Latency invariants are also
asserted: ``none``-kind scenarios carry no latency, flagged streamed
positives a finite one, unflagged streamed positives ``inf``.
Composes with ``--recorder-impl both`` (each impl gets its own
streamed-vs-post-hoc comparison).

``--mitigation NAME`` (repeatable) closes the detect → mitigate loop:
every detector × policy cell re-simulates the mitigated deployment and
the summary gains the recovered-throughput table.  The ``none`` control
is always included alongside the requested policies, and two gates run
(the mitigation smoke used in CI): the control's recovered fraction must
be exactly zero on every scenario, and on decisive core scenarios at
least one correct acted-on verdict must recover throughput.  With
``--streaming N`` the mitigation switches mid-stream at the first
flagged chunk instead of restarting from t=0:

    PYTHONPATH=src python examples/campaign_sweep.py \\
        --tiny --kinds core --kinds none --severities 10 \\
        --streaming 4 --mitigation remap
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.campaign import (CampaignGrid, _sev_str,  # noqa: E402
                                 run_campaign)
from repro.core.detectors import (DEFAULT_DETECTORS,  # noqa: E402
                                  available_detectors)
from repro.mitigate.policy import available_policies  # noqa: E402
from repro.core.recorder import RECORDER_IMPLS  # noqa: E402
from repro.core.sloth import SlothConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def make_grid(args) -> CampaignGrid:
    n_failures = tuple(args.n_failures) if args.n_failures else (1,)
    kinds = (tuple(args.kinds) if args.kinds
             else ("core", "link", "router", "none"))
    meshes = tuple(args.mesh or ()) + tuple(args.topology or ())
    if args.tiny:
        return CampaignGrid(workloads=("darknet19",),
                            meshes=meshes if meshes else (4,),
                            kinds=kinds,
                            severities=(tuple(args.severities)
                                        if args.severities else (8.0,)),
                            n_failures=n_failures,
                            reps=1, campaign_seed=args.seed)
    return CampaignGrid(
        workloads=("darknet19", "googlenet", "binary_tree"),
        meshes=meshes if meshes else (4, 6),
        kinds=kinds,
        severities=(tuple(args.severities) if args.severities
                    else (5.0, 10.0)),
        n_failures=n_failures,
        reps=2,
        campaign_seed=args.seed,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="minimal smoke grid (4 scenarios)")
    ap.add_argument("--seed", type=int, default=0, help="campaign seed")
    ap.add_argument("--workers", type=int, default=None,
                    help="pool width (default: cpu count)")
    ap.add_argument("--executor", choices=("thread", "process"),
                    default="thread",
                    help="scenario dispatch: GIL-bound thread pool or "
                         "true multi-core process pool (bit-identical "
                         "results either way)")
    ap.add_argument("--n-failures", type=int, action="append", default=None,
                    metavar="K", help="simultaneous-failure axis entry "
                    "(repeatable, e.g. --n-failures 1 --n-failures 2)")
    ap.add_argument("--mesh", action="append", default=None, metavar="WxH",
                    help="mesh axis entry, 'W' or 'WxH' "
                         "(repeatable, e.g. --mesh 12x12 --mesh 16x8)")
    ap.add_argument("--topology", action="append", default=None,
                    metavar="SPEC",
                    help="fabric axis entry, 'name:WxH[:variant]' with "
                         "name from the topology registry — mesh | torus "
                         "| systolic | het (repeatable, e.g. --topology "
                         "torus:8x8 --topology het:4x4:fast2slow1; "
                         "combines with --mesh entries on one axis)")
    ap.add_argument("--kinds", action="append", default=None, metavar="K",
                    help="failure-kind axis entry: core | link | router | "
                         "none | mixed | 'core+link'-style composite "
                         "(repeatable; default: all four base kinds)")
    ap.add_argument("--severities", action="append", default=None,
                    metavar="S", help="severity axis entry: a slowdown "
                    "factor or 'linspace:LO:HI:N' sweep spec (repeatable, "
                    "e.g. --severities 10 --severities linspace:1.5:3:4)")
    ap.add_argument("--detectors", action="append", default=None,
                    metavar="NAME", choices=available_detectors(),
                    help="detector to run on every scenario (repeatable; "
                         "default: sloth; see also --all-detectors)")
    ap.add_argument("--all-detectors", action="store_true",
                    help="shorthand for every registered detector "
                         "(SLOTH + the five baselines)")
    ap.add_argument("--streaming", type=int, default=0, metavar="N",
                    help="run SLOTH incrementally over N trace chunks per "
                         "scenario, report detection latency, and assert "
                         "streamed verdicts match a post-hoc campaign "
                         "(0 = post-hoc only, the default)")
    ap.add_argument("--mitigation", action="append", default=None,
                    metavar="NAME", choices=available_policies(),
                    help="mitigation policy to judge on every detector "
                         "verdict (repeatable; the 'none' control is "
                         "always added; default: no mitigation axis)")
    ap.add_argument("--recorder-impl", default="ref",
                    choices=RECORDER_IMPLS + ("both",),
                    help="SL-Recorder sketch path: per-run numpy oracle "
                         "(ref), on-device batched run-compressed scan "
                         "(batched), or 'both' to run the campaign twice "
                         "and assert identical verdicts + compression")
    args = ap.parse_args(argv)
    enable_compile_cache()

    detectors = (DEFAULT_DETECTORS if args.all_detectors
                 else tuple(args.detectors) if args.detectors
                 else ("sloth",))
    # the 'none' control rides along whenever mitigation is requested, so
    # the recovered-throughput table always has its zero baseline
    pols = (tuple(dict.fromkeys(tuple(args.mitigation) + ("none",)))
            if args.mitigation else ())
    grid = make_grid(args)
    n = grid.n_scenarios()
    print(f"campaign: {len(grid.workloads)} workloads × "
          f"{len(grid.meshes)} meshes × {len(grid.kinds)} kinds × "
          f"{len(grid.severities)} severities × "
          f"{len(grid.n_failures)} n_failures × {grid.reps} reps "
          f"= {n} scenarios (seed {grid.campaign_seed}, "
          f"executor {args.executor}, detectors {', '.join(detectors)}, "
          f"recorder {args.recorder_impl}"
          + (f", streaming {args.streaming} chunks" if args.streaming
             else "")
          + (f", mitigation {', '.join(pols)}" if pols else "") + ")")

    done = []

    def progress(o):
        done.append(o)
        if len(done) % 10 == 0 or len(done) == n:
            print(f"  ... {len(done)}/{n} scenarios", flush=True)

    cfg = (None if args.recorder_impl in ("ref", "both")
           else SlothConfig(recorder_impl=args.recorder_impl))
    t0 = time.perf_counter()
    res = run_campaign(grid, workers=args.workers, executor=args.executor,
                       detectors=detectors, cfg=cfg, progress=progress,
                       streaming=args.streaming, mitigation=pols)
    wall = time.perf_counter() - t0

    # explicit raises, not asserts, throughout the gates below: these are
    # the CI parity smokes and must still fail under python -O
    def judged(d):
        return (d.detector, d.flagged, d.pred_kind, d.pred_location,
                d.matched, d.truth_rank, d.truth_ranks)

    def check_streaming(streamed, label, campaign_cfg):
        """Streamed verdicts must equal a post-hoc campaign's, and
        detection latencies must obey the streaming semantics."""
        posthoc = run_campaign(grid, workers=args.workers,
                               executor=args.executor, detectors=detectors,
                               cfg=campaign_cfg)
        for s, p in zip(streamed.outcomes, posthoc.outcomes):
            for ds, dp in zip(s.detector_results, p.detector_results):
                if judged(ds) != judged(dp):
                    raise SystemExit(
                        f"streaming equivalence FAILED ({label}): "
                        f"scenario {s.scenario_id} "
                        f"streamed={judged(ds)} post-hoc={judged(dp)}")
                lat = ds.detection_latency
                if s.kind == "none":
                    if lat is not None:
                        raise SystemExit(
                            f"latency invariant FAILED ({label}): "
                            f"scenario {s.scenario_id} is failure-free "
                            f"but has latency {lat}")
                elif ds.detector == "sloth":
                    if lat is None:
                        raise SystemExit(
                            f"latency invariant FAILED ({label}): "
                            f"streamed positive scenario "
                            f"{s.scenario_id} has no latency")
                    if ds.flagged != (lat != float("inf")):
                        raise SystemExit(
                            f"latency invariant FAILED ({label}): "
                            f"scenario {s.scenario_id} flagged="
                            f"{ds.flagged} but latency {lat}")
        print(f"streaming equivalence ({label}): chunked == post-hoc on "
              f"all {len(streamed.outcomes)} scenarios")

    if args.streaming:
        check_streaming(res, args.recorder_impl
                        if args.recorder_impl != "both" else "ref", cfg)

    if pols:
        # mitigation smoke: the control recovers exactly nothing, and on
        # decisive core scenarios a correct acted-on verdict recovers
        # throughput
        for o in res.outcomes:
            for mo in o.mitigation_results:
                if mo.policy == "none" and mo.recovered_frac != 0.0:
                    raise SystemExit(
                        f"mitigation control FAILED: scenario "
                        f"{o.scenario_id} policy 'none' recovered "
                        f"{mo.recovered_frac} (must be exactly 0.0)")
        decisive = [mo for o in res.outcomes if o.kind == "core"
                    for mo in o.mitigation_results
                    if mo.policy != "none" and mo.correct and mo.acted]
        if decisive:
            recovered = [mo for mo in decisive if mo.recovered_frac > 0.0]
            if not recovered:
                raise SystemExit(
                    "mitigation smoke FAILED: no correct acted-on core "
                    "verdict recovered throughput under "
                    f"{', '.join(p for p in pols if p != 'none')}")
            print(f"mitigation smoke: control exactly 0.0 on all "
                  f"{len(res.outcomes)} scenarios; {len(recovered)}/"
                  f"{len(decisive)} acted core mitigations recovered "
                  f"throughput")

    if args.recorder_impl == "both":
        cfg_b = SlothConfig(recorder_impl="batched")
        res_b = run_campaign(grid, workers=args.workers,
                             executor=args.executor, detectors=detectors,
                             cfg=cfg_b, streaming=args.streaming)
        if args.streaming:
            check_streaming(res_b, "batched", cfg_b)
        for a, b in zip(res.outcomes, res_b.outcomes):
            if a.compression_ratio != b.compression_ratio:
                raise SystemExit(
                    f"recorder parity FAILED: scenario {a.scenario_id} "
                    f"compression ref={a.compression_ratio} "
                    f"batched={b.compression_ratio}")
            for da, db in zip(a.detector_results, b.detector_results):
                ka = judged(da) + (da.detection_latency,)
                kb = judged(db) + (db.detection_latency,)
                if ka != kb:
                    raise SystemExit(
                        f"recorder parity FAILED: scenario "
                        f"{a.scenario_id} ref={ka} batched={kb}")
        print(f"\nrecorder parity: ref == batched on all "
              f"{len(res.outcomes)} scenarios (verdicts, ranks, "
              f"compression ratios"
              + (", detection latencies" if args.streaming else "") + ")")

    print(f"\n== per-cell (workload, fabric, kind, severity, "
          f"n_failures) ==")
    for (wl, w, h, kind, sev, nf, topo), m in res.cells.items():
        if kind == "none":
            stat = f"FPR {m.fpr.pct():6.2f}% ({m.fpr.successes}/{m.fpr.trials})"
        else:
            stat = (f"acc {m.accuracy.pct():6.2f}% "
                    f"({m.accuracy.successes}/{m.accuracy.trials}) "
                    f"top3 {m.topk_rate(3)*100:6.2f}% "
                    f"recall@3 {m.recall_at(3)*100:6.2f}%")
        print(f"  {wl:12s} {topo}:{w}x{h} {kind:9s} x{_sev_str(sev):<8s} "
              f"k={nf} {stat}")

    if len(detectors) > 1:
        print(f"\n== per-detector (accuracy / FPR / top-3 / recall@3) ==")
        for name, m in res.detector_metrics.items():
            print(f"  {name:8s} acc {m.accuracy.pct():6.2f}% "
                  f"({m.accuracy.successes}/{m.accuracy.trials})  "
                  f"FPR {m.fpr.pct():6.2f}% "
                  f"({m.fpr.successes}/{m.fpr.trials})  "
                  f"top3 {m.topk_rate(3)*100:6.2f}%  "
                  f"recall@3 {m.recall_at(3)*100:6.2f}%")

    print(f"\n== campaign aggregate ==")
    print(res.summary())
    print(f"\nwall time: {wall:.1f}s "
          f"({wall / max(n, 1):.2f}s/scenario)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
