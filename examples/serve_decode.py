"""Serve a small model with batched requests (prefill + greedy decode).

    PYTHONPATH=src python examples/serve_decode.py
"""

import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, Request, ServeEngine

enable_compile_cache()
cfg = get_config("smollm-135m", smoke=True)
params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
engine = ServeEngine(cfg, params, EngineConfig(batch=4, cache_len=128))

rng = np.random.default_rng(0)
t0 = time.perf_counter()
for i in range(10):
    prompt = rng.integers(0, cfg.vocab, size=int(rng.integers(4, 24)))
    engine.submit(Request(i, prompt.astype(np.int32), max_new=12))
done = engine.run()
wall = time.perf_counter() - t0

for r in sorted(done, key=lambda r: r.rid)[:4]:
    print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out_tokens}")
tok = sum(len(r.out_tokens) for r in done)
print(f"\nserved {len(done)} requests, {tok} new tokens in {wall:.1f}s "
      f"({tok / wall:.0f} tok/s, CPU smoke config)")
print(f"mean decode step: {np.mean(engine.decode_times) * 1e3:.1f} ms "
      f"(mean prefill {np.mean(engine.prefill_times) * 1e3:.1f} ms)")
