"""A training configuration names its plain reference (``"reference":
"<name>"``, ``bench/reference/<name>.py``), and the training driver and
``train_mfu`` reach the model through it alone, so that a second model
joins the benchmark by new files."""

import json
import shutil
import sys
import textwrap
import time
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import flops  # noqa: E402
import harness as H  # noqa: E402
from reference import lm  # noqa: E402

SMOKE_MODEL = dict(hidden_size=48, intermediate_size=96, num_hidden_layers=2,
                   num_attention_heads=3, num_key_value_heads=1,
                   head_dim=16, vocab_size=256)

#: model FLOPs of one SmolLM-135M training step at batch 8 x seq 512
SMOLLM_FLOPS_PER_STEP = 3_739_842_772_992


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(cell, steps=100, window_s=10.0):
    out = H.Outcome(end_to_end={}, checks=[], attempted=steps, failed=0,
                    window=(0.0, window_s),
                    extra={"counters": {"steps": steps,
                                        "window_s": window_s}})
    return H.Run(cell, H.Recorder(), out, None, {"bf16_flops_per_s": 197e12})


def test_the_smollm_cell_resolves_to_the_llama_reference():
    """Weights, tokens and the reference's three steps through the cell's
    reference are, bit for bit, those of ``reference/lm.py`` called
    directly."""
    import jax

    cell = H.resolve_cell("smollm135m-train-plain")
    ref = cell.reference
    assert Path(ref.__file__) == BENCH / "reference" / "lm.py"
    assert cell.reference is ref
    m = dict(cell.config["model"], **SMOKE_MODEL)
    o = cell.config["train"]["optimizer"]
    seed = 2 ** 31 + 11
    a, b = ref.make_params_from_seed(m, seed), lm.make_params_from_seed(m,
                                                                        seed)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.array_equal(ref.batch(seed, 2, 2, 32, 256),
                          lm.batch(seed, 2, 2, 32, 256))
    got = ref.run_reference(m, o, seed, 2, 32)
    want = lm.run_reference(m, o, seed, 2, 32)
    assert got["losses"] == want["losses"]
    for k in ("grad_norms", "delta_norms"):
        assert np.array_equal(got[k], want[k])


@pytest.mark.parametrize("name", ["smollm135m-train-plain",
                                  "smollm135m-train-monitored"])
def test_train_mfu_reads_the_references_flops(name):
    cell = H.resolve_cell(name)
    per_step = cell.reference.train_flops_per_step(cell.config["model"], 8,
                                                   512)
    assert per_step == flops.train_flops_per_step(cell.config["model"], 8,
                                                  512)
    assert per_step == SMOLLM_FLOPS_PER_STEP
    got = H.reader_for(cell, "train_mfu").read(_run(cell))
    assert got == 100.0 * SMOLLM_FLOPS_PER_STEP * 100 / 10.0 / 197e12


def test_a_reference_without_a_flop_count_leaves_train_mfu_out():
    cell = H.resolve_cell("smollm135m-train-plain")
    cell.reference = types.SimpleNamespace()
    assert H.reader_for(cell, "train_mfu").read(_run(cell)) is None


@pytest.mark.parametrize("case", ["no_key", "no_file"])
def test_a_missing_reference_is_an_error_naming_the_path(case):
    cell = H.resolve_cell("smollm135m-train-plain")
    if case == "no_key":
        del cell.config["reference"]
        path = "configs/smollm135m-train.json"
    else:
        cell.config["reference"] = "no_such_model"
        path = "reference/no_such_model.py"
    with pytest.raises(H.BenchError, match=path):
        cell.reference
    ctx = H.Context(cell, 1, 0.1, False, Path("unused"), None,
                    time.perf_counter())
    with pytest.raises(H.BenchError, match=path):
        H.driver_for(cell).run(ctx)


SPY = '''
"""A training reference that is the Llama one, counting its calls."""

from reference import lm

calls = []


def _counted(name):
    def call(*args, **kw):
        calls.append(name)
        return getattr(lm, name)(*args, **kw)
    return call


_Frozen = lm._Frozen
for _name in ("init_params", "make_params_from_seed", "leaf_norms", "batch",
              "run_reference"):
    globals()[_name] = _counted(_name)


def train_flops_per_step(model, batch, seq):
    calls.append("train_flops_per_step")
    return 1e9 * batch * seq
'''


def test_a_new_reference_joins_by_new_files_alone(tmp_path):
    """A configuration, its reference, traffic and limits added as new
    files, plus entries in ``BENCHMARK.json``: the driver and
    ``train_mfu`` call that reference, and no file is edited."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    config = json.loads((BENCH / "configs" / "smollm135m-train.json")
                        .read_text())
    config.update(reference="spy", launcher_args=["--smoke"],
                  model=dict(config["model"], **SMOKE_MODEL))
    (bench_dir / "configs" / "spy-train.json").write_text(json.dumps(config))
    (bench_dir / "reference" / "spy.py").write_text(textwrap.dedent(SPY))
    (bench_dir / "traffic" / "tiny.json").write_text(json.dumps(dict(
        json.loads((BENCH / "traffic" / "plain.json").read_text()),
        batch=2, seq=32)))
    (bench_dir / "limits" / "spy-train-tiny.json").write_text(
        (BENCH / "limits" / "smollm135m-train-plain.json").read_text())
    bench = H.load_benchmark()
    bench["configs"].append({"name": "spy-train", "source": "x",
                             "file": "bench/configs/spy-train.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "spy-train-tiny", "config":
                               "spy-train", "traffic": "tiny", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm135m-train-plain" in m.get("workloads", ()):
            m["workloads"].append("spy-train-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = H.resolve_cell("spy-train-tiny", bench_dir)
    spy = cell.reference
    assert Path(spy.__file__) == bench_dir / "reference" / "spy.py"
    assert "train_mfu" in [m["name"] for m in cell.per_layer]
    ctx = H.Context(cell, 2 ** 31 + 5, 0.3, False, Path("unused"),
                    {"bf16_flops_per_s": 197e12}, time.perf_counter())
    outcome = H.driver_for(cell).run(ctx)
    assert outcome.checks and all(c.ok for c in outcome.checks)
    assert {"init_params", "make_params_from_seed", "leaf_norms", "batch",
            "run_reference"} <= set(spy.calls)
    run = H.Run(cell, ctx.rec, outcome, None, ctx.peak)
    steps, window_s = outcome.extra["counters"]["steps"], \
        outcome.extra["counters"]["window_s"]
    got = H.reader_for(cell, "train_mfu").read(run)
    assert got == 100.0 * 1e9 * 2 * 32 * steps / window_s / 197e12
    assert "train_flops_per_step" in spy.calls
    assert {p: p.read_bytes() for p in before} == before
