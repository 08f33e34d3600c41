"""The on-chip benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix, driver and per-layer readers are files under ``bench/`` found by
name (see ``bench/harness.py``).  The run needs a TPU: without one, or
with fewer chips than the cell asks for, it exits nonzero and prints no
result.  Set-up (making the inputs, warming every shape the traffic uses)
is timed as ``setup_s``; then the driver measures for ``--seconds``.
With ``--trace 1`` a profiler trace runs through the window and the
result carries the cell's per-layer metrics; with ``--trace 0`` its
end-to-end metrics.  Once the window has closed the driver compares what
the timed path produced with the plain reference; every number compared
is printed beside its limit as the last lines on standard error and
under ``checks`` in the result, which is the last line on standard
output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

#: JAX's persistent compilation cache: the one the environment names, else
#: a fixed directory inside the checkout, so only the first run compiles.
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
    ROOT / ".jax_cache")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    import harness as H
    try:
        cell = H.resolve_cell(args.workload, BENCH_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = H.require_chips(cell.chips)
        peak = H.load_peak(devices[0].device_kind, BENCH_DIR)
        driver = H.driver_for(cell)
        out_dir = ROOT / ".bench_out" / cell.name
        ctx = H.Context(cell, args.seed, args.seconds, bool(args.trace),
                        out_dir, peak, T_START)
        ctx.rec.listen_compiles()
        # the program's own prints go to standard error: standard output
        # ends with the result line
        with contextlib.redirect_stdout(sys.stderr):
            outcome = driver.run(ctx)
            ctx.reduce_trace()
            metrics = H.metric_values(cell, ctx, outcome)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    line = H.result_line(cell, ctx, outcome, metrics, devices)
    H.print_checks(outcome.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
