"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this prints/records:
  * compiled memory analysis (bytes per device — proves it fits),
  * cost analysis (HLO FLOPs / bytes for the roofline),
  * collective bytes parsed from the optimized HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute),
  * the three roofline terms for TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI) and the dominant bottleneck.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all          # orchestrates subprocesses
Results accumulate in dryrun_results.json (one entry per cell) so an
interrupted sweep resumes where it stopped.

This is a CPU-only compile tool: ``main()`` pins JAX to the CPU with 512
forced host devices before anything imports JAX, and the per-cell
subprocesses inherit that environment, so no process of a sweep reaches
for an attached chip.
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

# hardware constants (TPU v5e)
PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link (per chip, per direction)

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "dryrun_results.json")
RESULTS_PATH = os.path.abspath(
    os.environ.get("DRYRUN_RESULTS", RESULTS_PATH))

def roofline(flops, hbm_bytes, coll_bytes, n_chips):
    t_compute = flops / (n_chips * PEAK_FLOPS)
    t_memory = hbm_bytes / (n_chips * HBM_BW)
    t_coll = coll_bytes / (n_chips * LINK_BW)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    return terms, dom


def run_cell(arch: str, shape: str, mesh_kind: str, plan_overrides=None):
    import jax
    from ..configs.base import get_config
    from . import steps
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    reason = steps.skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    plan = steps.default_plan(cfg, shape)
    if plan_overrides:
        import dataclasses as dc
        plan = dc.replace(plan, **plan_overrides)

    t0 = time.time()
    fn, args, in_sh, out_sh = steps.build_cell(cfg, shape, mesh, plan)
    donate = ()
    if shape in ("train_4k",):
        donate = (0, 1)          # params + optimizer state
    elif steps.SHAPES[shape]["kind"] == "decode":
        donate = (2,)            # KV/SSM cache updated in place
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware analysis (cost_analysis counts scan bodies once)
    from .hlo_analysis import analyze_hlo
    ana = analyze_hlo(hlo)

    flops = float(ana["flops"])          # per device, loop-scaled
    flops_global = flops * n_chips
    hbm = float(ana["hbm_bytes"])
    hbm_global = hbm * n_chips
    coll = {"total": ana["collective_total"],
            "per_op": ana["collective_bytes"],
            "counts": ana["collective_counts"]}
    coll_global = coll["total"] * n_chips

    terms, dom = roofline(flops_global, hbm_global, coll_global, n_chips)

    n = cfg.param_count()
    n_active = cfg.active_param_count()
    sh = steps.SHAPES[shape]
    tokens = sh["batch"] * (sh["seq"] if sh["kind"] != "decode" else 1)
    mult = 6 if sh["kind"] == "train" else 2
    model_flops = mult * n_active * tokens

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "status": "ok",
        "n_chips": int(n_chips),
        "plan": {k: str(v) for k, v in vars(plan).items()},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            # args live for the whole step; outputs are materialised at the
            # end; peak_memory is XLA's live-set maximum for temps
            "peak_bytes": int(
                getattr(mem, "peak_memory_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)),
            "xla_flops_1trip": float(cost.get("flops", 0.0)),
            "flops": flops, "hbm_bytes": hbm,
            "collective_bytes": coll["total"],
        },
        "collectives": {"counts": coll["counts"],
                        "bytes": coll["per_op"]},
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dom,
            "model_flops": model_flops,
            "useful_flops_frac": (model_flops / flops_global
                                  if flops_global else 0.0),
        },
    }
    return rec


def load_results() -> dict:
    if os.path.exists(RESULTS_PATH):
        with open(RESULTS_PATH) as f:
            return json.load(f)
    return {}


def save_result(key: str, rec: dict):
    res = load_results()
    res[key] = rec
    tmp = RESULTS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(res, f, indent=1, sort_keys=True)
    os.replace(tmp, RESULTS_PATH)


def orchestrate(archs, shapes, meshes, force=False, variant="",
                plan_overrides=None):
    """Run each cell in a fresh subprocess (bounds compile-cache memory)."""
    done = load_results()
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                key = f"{arch}|{shape}|{mesh}" + (f"|{variant}" if variant
                                                  else "")
                if key in done and not force \
                        and done[key].get("status") in ("ok", "skipped"):
                    print(f"[skip cached] {key}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh]
                if variant:
                    cmd += ["--variant", variant]
                if plan_overrides:
                    cmd += ["--plan", json.dumps(plan_overrides)]
                print(f"[run] {key}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=3600)
                if r.returncode != 0:
                    save_result(key, {"arch": arch, "shape": shape,
                                      "mesh": mesh, "status": "error",
                                      "error": r.stderr[-4000:]})
                    print(f"  ERROR (recorded): {r.stderr.splitlines()[-1] if r.stderr else '?'}")
                else:
                    print("  " + (r.stdout.strip().splitlines()[-1]
                                  if r.stdout.strip() else "ok"))


def main():
    # CPU with 512 virtual devices, for this process and the cell
    # subprocesses it spawns (they inherit os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", default="")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="label suffix for plan-override experiments")
    ap.add_argument("--plan", default="",
                    help="JSON CellPlan overrides, e.g. "
                         "'{\"expert_parallel\": true}'")
    args = ap.parse_args()

    overrides = json.loads(args.plan) if args.plan else None

    if args.all or args.archs or args.shapes:
        from ..configs.base import list_archs
        from .steps import SHAPES
        archs = args.archs.split(",") if args.archs else list_archs()
        shapes = args.shapes.split(",") if args.shapes else list(SHAPES)
        meshes = args.meshes.split(",")
        orchestrate(archs, shapes, meshes, force=args.force,
                    variant=args.variant, plan_overrides=overrides)
        return

    rec = run_cell(args.arch, args.shape, args.mesh, overrides)
    key = f"{args.arch}|{args.shape}|{args.mesh}" + \
        (f"|{args.variant}" if args.variant else "")
    save_result(key, rec)
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(json.dumps({
            "cell": key,
            "peak_GiB": round(rec["per_device"]["peak_bytes"] / 2**30, 2),
            "compute_s": f"{r['compute_s']:.3e}",
            "memory_s": f"{r['memory_s']:.3e}",
            "collective_s": f"{r['collective_s']:.3e}",
            "dominant": r["dominant"],
            "useful_flops_frac": round(r["useful_flops_frac"], 3),
            "compile_s": rec["compile_s"],
        }))
    else:
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
