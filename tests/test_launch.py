"""Launch layer: cell specs, sharding sanitisation, HLO analysis, and a
1-device lowering smoke test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config, list_archs
from repro.launch import steps
from repro.launch.hlo_analysis import analyze_hlo

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def test_shapes_table():
    assert set(steps.SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                                 "long_500k"}
    assert steps.SHAPES["long_500k"]["seq"] == 524288
    assert steps.SHAPES["train_4k"]["batch"] == 256


def test_skip_rules():
    skipped = {a for a in list_archs()
               if steps.skip_reason(get_config(a), "long_500k")}
    assert skipped == {"qwen2-vl-2b", "whisper-large-v3", "yi-34b",
                       "smollm-135m", "stablelm-1.6b", "dbrx-132b",
                       "moonlight-16b-a3b", "moonlight-16b-a3b-ep8"}
    for a in list_archs():
        for sh in ("train_4k", "prefill_32k", "decode_32k"):
            assert steps.skip_reason(get_config(a), sh) is None


@pytest.mark.parametrize("arch", list_archs())
def test_input_specs_all_cells(arch):
    cfg = get_config(arch)
    for shape in steps.SHAPES:
        if steps.skip_reason(cfg, shape):
            continue
        plan = steps.default_plan(cfg, shape)
        specs = steps.input_specs(cfg, shape, plan)
        assert "tokens" in specs
        sh = steps.SHAPES[shape]
        if sh["kind"] == "decode":
            assert specs["tokens"].shape == (sh["batch"], 1)
            assert "cache" in specs
        else:
            assert specs["tokens"].shape == (sh["batch"], sh["seq"])


def test_sanitize_drops_indivisible_axes():
    from repro.models.sharding import sanitize
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16, "data": 16}
    spec = sanitize(P("model", None), (50280, 2048), FakeMesh())
    assert spec == P(None, None)
    spec = sanitize(P("model", "data"), (64000, 4096), FakeMesh())
    assert spec == P("model", "data")


def test_hlo_analysis_counts_scan_trips():
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((9, 128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    r = analyze_hlo(compiled.as_text())
    expect = 2 * 128 ** 3 * 9
    assert abs(r["flops"] - expect) / expect < 0.01


def test_lowering_smoke_single_device():
    """A reduced config lowers + compiles on the 1-device debug mesh with
    the same build_cell machinery the 512-chip dry-run uses."""
    import dataclasses
    from repro.launch.mesh import make_debug_mesh
    cfg = get_config("smollm-135m", smoke=True)
    mesh = make_debug_mesh(1, 1)
    plan = steps.CellPlan(grad_accum=1, remat=False,
                          param_dtype=jnp.float32)
    # shrink the shape table for the smoke lowering
    orig = steps.SHAPES["train_4k"]
    steps.SHAPES["train_4k"] = dict(kind="train", seq=32, batch=4)
    try:
        fn, args, in_sh, out_sh = steps.build_cell(cfg, "train_4k", mesh,
                                                   plan)
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh,
                               out_shardings=out_sh).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):       # older jax: one per device
            ca = ca[0]
        assert ca["flops"] > 0
    finally:
        steps.SHAPES["train_4k"] = orig


def test_production_mesh_shapes():
    """Mesh construction logic (validated against the real 512-device
    config by the dry-run; here we only check shape arithmetic)."""
    import repro.launch.mesh as M
    import inspect
    src = inspect.getsource(M.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


def test_dryrun_results_complete():
    """The committed dry-run sweep covers every (arch × shape × mesh) cell
    with ok or a documented skip."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_results.json")
    if not os.path.exists(path):
        pytest.skip("dry-run results not generated yet")
    data = json.load(open(path))
    for arch in list_archs():
        for shape in steps.SHAPES:
            for mesh in ("single", "multi"):
                key = f"{arch}|{shape}|{mesh}"
                assert key in data, f"missing cell {key}"
                assert data[key]["status"] in ("ok", "skipped"), key
                if data[key]["status"] == "skipped":
                    assert steps.skip_reason(get_config(arch), shape)
