"""Compile the main path's device programs for a TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a described
``v5e:2x2`` topology: what it refuses here would be refused on the chip.
Each test lowers one program at its deployed size onto one described
chip and compiles it — the on-device batched recorder at the pod
telemetry geometry, FailRank's jitted loop, and the four Pallas kernels
with ``interpret=False`` (each must lower to a ``tpu_custom_call``).
Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  The persistent
compilation cache is off around these compiles, since an executable for a
described chip cannot be read back here.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sketch import SketchParams

# the pod detector's deployed sketch geometry (PodTelemetryConfig)
POD_SKETCH = SketchParams(d=2, m=1024, H=4, L=2048)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def shape(one_chip):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _shapes_of(make, shape):
    """Described-chip shapes of the arrays ``make()`` would build."""
    return jax.tree.map(lambda a: shape(a.shape, a.dtype),
                        jax.eval_shape(make))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_scoped_loop(compiled, scope):
    """The program's loop carries ``scope`` in its op name, which is how
    a profiler trace's device op is found by name."""
    assert re.search(r'= .*\bwhile\(.*op_name="[^"]*/' + re.escape(scope)
                     + '/while"', compiled.as_text())


def test_batched_recorder_compiles(shape):
    from repro.kernels.sketch_update import batched, ref
    n = 4096
    state = _shapes_of(lambda: ref.make_state(POD_SKETCH), shape)
    drain = _shapes_of(lambda: ref.make_drain(n), shape)
    i32 = shape((n,), jnp.int32)
    f32 = shape((n,))
    compiled = batched.insert_runs_vectorized.lower(
        state, drain, i32, i32, i32, f32, f32, f32, f32,
        H=POD_SKETCH.H).compile()
    assert compiled.memory_analysis() is not None
    _assert_scoped_loop(compiled, "sketch.scan")


def test_failrank_loop_compiles(shape):
    from repro.core.failrank import FailRankParams, _failrank_iterate
    n_nodes, n_edges = 1024, 4096       # a padded pod-window MCG
    p = FailRankParams()
    compiled = _failrank_iterate.lower(
        shape((n_nodes,)), shape((n_edges,)), shape((n_edges,)),
        shape((n_edges,), jnp.int32), shape((n_edges,), jnp.int32),
        p.lam, p.alpha, p.beta, p.gamma, p.eps, p.max_iters).compile()
    assert compiled.memory_analysis() is not None
    _assert_scoped_loop(compiled, "failrank.iterate")


def test_failrank_step_kernel_compiles(shape):
    from repro.kernels.failrank_step.kernel import failrank_step
    n = 512
    _assert_kernel(failrank_step.lower(
        shape((n, n)), shape((n, n)), shape((n,)), shape((n,)),
        interpret=False).compile())


def test_flash_attention_kernel_compiles(shape):
    from repro.kernels.flash_attention.ops import gqa_attention
    b, s, hq, hk, d = 1, 512, 9, 3, 64      # SmolLM-135M attention
    fn = jax.jit(partial(gqa_attention, impl="pallas", interpret=False))
    _assert_kernel(fn.lower(shape((b, s, hq, d)), shape((b, s, hk, d)),
                            shape((b, s, hk, d))).compile())


def test_ssd_scan_kernel_compiles(shape):
    from repro.kernels.ssd_scan.kernel import ssd_scan
    bh, s, p, n = 8, 512, 64, 128
    _assert_kernel(ssd_scan.lower(
        shape((bh, s, p)), shape((bh, s)), shape((bh,)),
        shape((bh, s, n)), shape((bh, s, n)), chunk=128,
        interpret=False).compile())


@pytest.mark.parametrize("cap", [1, 256])
def test_sketch_kernel_compiles(shape, cap):
    from repro.kernels.sketch_update import kernel, ref
    n = 4096
    state = _shapes_of(lambda: ref.make_state(POD_SKETCH), shape)
    drain = _shapes_of(lambda: ref.make_drain(cap), shape)
    i32 = shape((n,), jnp.int32)
    f32 = shape((n,))
    _assert_kernel(kernel.sketch_insert.lower(
        state, i32, i32, f32, f32, f32, params=POD_SKETCH, block=256,
        interpret=False, drain=drain).compile())

