"""Benchmark harness: one bench per paper table/figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV.  Env: BENCH_FULL=1 for paper-scale
datasets; BENCH_ONLY=<substring> to run a subset.  A bench that raises
still prints its EXCEPTION row, and the harness then exits nonzero.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from benchmarks.kernels import bench_kernels
    from benchmarks.paper import ALL as PAPER_BENCHES
    from benchmarks.roofline import bench_roofline
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    benches = list(PAPER_BENCHES) + [bench_kernels, bench_roofline]
    only = os.environ.get("BENCH_ONLY", "")
    print("name,us_per_call,derived")
    failed = []
    for bench in benches:
        if only and only not in bench.__name__:
            continue
        t0 = time.time()
        try:
            rows = bench()
        except Exception as e:  # report, run the rest, fail at the end
            print(f"{bench.__name__},0,EXCEPTION:{type(e).__name__}:{e}")
            failed.append(bench.__name__)
            continue
        for name, us, derived in rows:
            print(f"{name},{us},{derived}")
        print(f"#{bench.__name__}_wall_s,{time.time() - t0:.1f},")
        sys.stdout.flush()
    if failed:
        sys.exit(f"benchmarks raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
