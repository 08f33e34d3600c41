"""Quickstart: detect an injected fail-slow failure with SLOTH.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.core.failures import FailSlow
from repro.core.graph import build_workload
from repro.core.routing import Mesh2D
from repro.core.sloth import Sloth

enable_compile_cache()

# 1. a 4×4 many-core accelerator running ResNet-50 inference
mesh = Mesh2D(4)
graph = build_workload("resnet50")
sloth = Sloth(graph, mesh)
print(f"workload: {graph}")
print("probe plan generated by SL-Compiler:")
print(sloth.plan.describe())

# 2. inject a 10× fail-slow on core 6 between t=1s and t=9s
failure = FailSlow(kind="core", location=6, t0=1.0, duration=8.0,
                   slowdown=10.0)
verdict = sloth.detect([failure], seed=0)

# 3. the verdict
print(f"\nflagged:   {verdict.flagged}")
print(f"root cause: {verdict.kind} {verdict.location} "
      f"(injected: {failure.kind} {failure.location})")
print(f"top-3 ranking: {[(k, l, round(s, 3)) for k, l, s in verdict.ranking[:3]]}")
print(f"FailRank iterations: {verdict.failrank.iterations}")
print(f"trace compression: {verdict.recorder.compression_ratio:.1f}x "
      f"({verdict.recorder.raw_bytes/1024:.0f} KiB -> "
      f"{verdict.recorder.sketch_bytes/1024:.0f} KiB)")
assert verdict.matches(failure), "detection failed!"
print("\nOK: root cause correctly localised.")
