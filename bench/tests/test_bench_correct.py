"""What decides ``correct``, at sizes a test run holds, on the CPU.

* The control, the reference computed in bfloat16 in the program's
  place, fails at least one number of each cell's comparison.
* A run whose timed path is broken underneath comes out not correct, for
  each fault the cell can have: a step that returns its state unchanged,
  half of the batch left out, an answer altered where it is produced.
  In the monitored training cell that holds for the telemetry's recorder
  and verdicts as well as for the training step.
  (No cell spans chips, so there is no exchange between chips to leave
  out.)  The run skips only the harness's look for a chip.
"""

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402

SMOKE_MODEL = dict(hidden_size=48, intermediate_size=96, num_hidden_layers=2,
                   num_attention_heads=3, num_key_value_heads=1,
                   head_dim=16, vocab_size=256)


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch, tmp_path):
    """The program's launcher keeps its compile cache where the environment
    says; here, out of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def small_cell(name: str) -> H.Cell:
    """The cell at a size the CPU runs in seconds: a 4x4 pod with 8-step
    windows, or the program's smoke-sized model at batch 2 x seq 32."""
    cell = H.resolve_cell(name)
    if cell.config["driver"] == "pod":
        cell.config.update(mesh_w=4, mesh_h=4, window_steps=8)
        cell.traffic["windows"] = 2
        if cell.traffic["failure"]:
            cell.traffic["failure"] = dict(cell.traffic["failure"],
                                           location=5)
    else:
        cell.config["launcher_args"] = ["--smoke"]
        cell.config["model"] = dict(cell.config["model"], **SMOKE_MODEL)
        cell.traffic.update(batch=2, seq=32)
    return cell


def run_cell(cell: H.Cell, seed: int = 2 ** 31 + 99,
             seconds: float = 0.3) -> dict:
    """A run of ``cell`` as ``bench/run.py`` makes it, without the chip."""
    ctx = H.Context(cell, seed, seconds, False, Path("unused"),
                    {"bf16_flops_per_s": 197e12}, time.perf_counter())
    outcome = H.driver_for(cell).run(ctx)
    metrics = H.metric_values(cell, ctx, outcome)

    class Dev:
        platform, device_kind = "cpu", "cpu"

    return H.result_line(cell, ctx, outcome, metrics, [Dev()])


def limits_of(cell):
    return cell.limits


def failed_numbers(nums: dict, limits: dict) -> list[str]:
    return [k for k, v in nums.items() if v > limits[k]]


# ---------------------------------------------------------------------------
# the control fails
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["pod16-slowchip", "pod16-healthy",
                                  "pod16-aged"])
def test_pod_control_fails_and_program_passes(name):
    cell = small_cell(name)
    r = H.driver_for(cell).calibrate(cell, 31337)
    lim = limits_of(cell)
    assert failed_numbers(r["program"], lim) == []
    assert failed_numbers(r["control"], lim)


def test_train_control_fails():
    import jax.numpy as jnp
    from reference import lm

    cell = small_cell("smollm135m-train-plain")
    drv = H.driver_for(cell)
    m, o = cell.config["model"], cell.config["train"]["optimizer"]
    want = lm.run_reference(m, o, 7, 2, 32)
    ctl = lm.run_reference(m, o, 7, 2, 32, dtype=jnp.bfloat16)
    nums = drv.compare_steps(ctl["losses"], ctl["grad_norms"],
                             ctl["delta_norms"], want)
    assert failed_numbers(nums, limits_of(cell))


def test_telemetry_control_fails_and_program_passes():
    cell = small_cell("smollm135m-train-monitored")
    cell.traffic["calibrate_seconds"] = 1.0
    r = H.driver_for(cell).calibrate(cell, 4242)
    assert "telemetry_pattern_gap" in r["program"]
    assert failed_numbers(r["program"], limits_of(cell)) == []
    assert failed_numbers(r["control"], limits_of(cell))


# ---------------------------------------------------------------------------
# sound runs pass, broken runs fail
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["pod16-slowchip", "pod16-healthy",
                                  "pod16-aged", "smollm135m-train-plain",
                                  "smollm135m-train-monitored"])
def test_a_sound_run_is_correct(name):
    line = run_cell(small_cell(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0


def _pod_fault(monkeypatch, fault: str):
    from repro.core import streaming
    from repro.core.simulator import SimResult
    from repro.distributed import telemetry as tel

    orig_observe = streaming.StreamingRecorder.observe
    orig_pod = tel.PodDetector.observe
    if fault == "state_unchanged":
        monkeypatch.setattr(streaming.StreamingRecorder, "observe",
                            lambda self, chunk: None)
    elif fault == "half_batch":
        def half(self, chunk):
            cut = {k: {c: v[:len(v) // 2] for c, v in side.items()}
                   for k, side in (("comp", chunk.comp),
                                   ("comm", chunk.comm))}
            return orig_observe(self, SimResult(
                chunk.total_time, cut["comp"], cut["comm"],
                chunk.n_raw_records // 2))
        monkeypatch.setattr(streaming.StreamingRecorder, "observe", half)
    else:
        def altered(self, window):
            v = orig_pod(self, window)
            return dataclasses.replace(v, flagged=True, kind="core",
                                       location=((v.location or 0) + 1))
        monkeypatch.setattr(tel.PodDetector, "observe", altered)


@pytest.mark.parametrize("name", ["pod16-slowchip", "pod16-healthy",
                                  "pod16-aged"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_broken_pod_run_is_not_correct(monkeypatch, name, fault):
    _pod_fault(monkeypatch, fault)
    line = run_cell(small_cell(name))
    assert line["correct"] is False, (fault, line["checks"])


def _train_fault(monkeypatch, fault: str):
    import jax.numpy as jnp
    from repro.launch import steps
    from repro.optim import adamw

    orig_loss = steps.loss_fn
    if fault == "state_unchanged":
        monkeypatch.setattr(adamw, "apply", lambda params, grads, state, cfg:
                            (params, state, {"grad_norm": jnp.zeros(())}))
    elif fault == "half_batch":
        monkeypatch.setattr(
            steps, "loss_fn",
            lambda cfg, params, tokens, *a, **kw: orig_loss(
                cfg, params, tokens[:tokens.shape[0] // 2], *a, **kw))
    else:
        def altered(*a, **kw):
            import jax
            total, ce = orig_loss(*a, **kw)
            return total + 0.01 * jax.lax.stop_gradient(total), ce
        monkeypatch.setattr(steps, "loss_fn", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_broken_training_run_is_not_correct(monkeypatch, fault):
    _train_fault(monkeypatch, fault)
    line = run_cell(small_cell("smollm135m-train-plain"))
    assert line["correct"] is False, (fault, line["checks"])


def test_a_broken_telemetry_verdict_is_not_correct(monkeypatch):
    _pod_fault(monkeypatch, "answer_altered")
    line = run_cell(small_cell("smollm135m-train-monitored"), seconds=1.0)
    assert line["checks"]["telemetry_verdicts_off"]["value"] > 0
    assert line["correct"] is False


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_telemetry_recorder_is_not_correct(monkeypatch, fault):
    """The telemetry's recorder keeping nothing, or half of each window's
    records: its patterns no longer match the reference's."""
    _pod_fault(monkeypatch, fault)
    line = run_cell(small_cell("smollm135m-train-monitored"), seconds=1.0)
    assert line["checks"]["telemetry_pattern_gap"]["value"] > \
        line["checks"]["telemetry_pattern_gap"]["limit"], line["checks"]
    assert line["correct"] is False


def test_tokens_and_weights_follow_the_seed():
    from reference import lm
    assert (lm.batch(5, 3, 2, 32, 256) == lm.batch(5, 3, 2, 32, 256)).all()
    assert not (lm.batch(5, 3, 2, 32, 256) == lm.batch(6, 3, 2, 32, 256)
                ).all()
    a = lm.make_params_from_seed(SMOKE_MODEL | {"rms_norm_eps": 1e-6,
                                                "rope_theta": 1e4}, 9)
    b = lm.make_params_from_seed(SMOKE_MODEL | {"rms_norm_eps": 1e-6,
                                                "rope_theta": 1e4}, 9)
    assert all(np.array_equal(x, y) for x, y in
               zip(np.asarray(a["embed"]), np.asarray(b["embed"])))
