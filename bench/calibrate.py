"""Readings of a cell's correctness numbers over many seeds, in one
process: the program's (which set each limit's lower reading) and the
control's (its upper reading).  Needs the cell's chips, like a run.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ...

Prints one JSON line per seed and, last, the largest program reading and
the smallest reading of the control (and of any fault the driver reads)
of each number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness as H
    cell = H.resolve_cell(args.workload, BENCH_DIR)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        BENCH_DIR.parent / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    H.require_chips(cell.chips)
    driver = H.driver_for(cell)
    worst: dict = {}
    for seed in args.seeds:
        r = driver.calibrate(cell, seed)
        print(json.dumps({"seed": seed, **r}), flush=True)
        for side, nums in r.items():
            pick = max if side == "program" else min
            w = worst.setdefault(side, {})
            for k, v in nums.items():
                w[k] = pick(w.get(k, v), v)
    print(json.dumps({"workload": cell.name, "worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
