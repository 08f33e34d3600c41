"""The harness's contract: its arguments, its result line, its refusal to
run without a TPU, and files found by name."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness as H  # noqa: E402


def _run_py():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [
    [],
    ["--workload", "pod16-slowchip", "--seed", "1", "--seconds", "10"],
    ["--workload", "pod16-slowchip", "--seed", "1", "--seconds", "10",
     "--trace", "2"],
    ["--workload", "pod16-slowchip", "--seed", "x", "--seconds", "10",
     "--trace", "0"],
    ["--workload", "pod16-slowchip", "--seed", "-1", "--seconds", "10",
     "--trace", "0"],
    ["--workload", "pod16-slowchip", "--seed", "1", "--seconds", "0",
     "--trace", "0"],
])
def test_bad_arguments_are_refused(argv):
    with pytest.raises(SystemExit) as e:
        _run_py().parse_args(argv)
    assert e.value.code != 0


def test_the_drivers_arguments_parse():
    a = _run_py().parse_args(["--workload", "pod16-healthy", "--seed",
                              str(2 ** 31 + 12345), "--seconds", "45",
                              "--trace", "1"])
    assert (a.workload, a.seed, a.seconds, a.trace) == (
        "pod16-healthy", 2 ** 31 + 12345, 45.0, 1)


def test_every_cell_resolves_with_its_metrics():
    bench = H.load_benchmark()
    for w in bench["workloads"]:
        cell = H.resolve_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names
            assert (BENCH / "layers" / f"{m['name']}.py").is_file()
        assert (BENCH / "drivers" / f"{cell.config['driver']}.py").is_file()


def test_every_limit_has_the_readings_it_was_set_from():
    """Each cell's limits sit beside their readings: above the largest
    reading of sound runs, below the smallest of the control or a fault
    (an exact comparison has the limit 0)."""
    for w in H.load_benchmark()["workloads"]:
        f = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        assert f["limits"], w["name"]
        for name, limit in f["limits"].items():
            r = f["readings"][name]
            if r.get("exact"):
                assert limit == 0 and r["upper"] > 0
            else:
                assert r["lower"] < limit < r["upper"], (w["name"], name)


def _outcome():
    return H.Outcome(end_to_end={"verdict_s": 1.5, "setup_s": 30.0},
                     checks=[H.Check("gap", 1e-7, 1e-5),
                             H.Check("off", 0, 0)],
                     attempted=4, failed=0, window=(0.0, 1.0))


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_result_line_keys_and_checks_last(tmp_path):
    cell = H.resolve_cell("pod16-slowchip")
    ctx = H.Context(cell, 1, 1.0, False, tmp_path, None, 0.0)
    out = _outcome()
    metrics = H.metric_values(cell, ctx, out)
    line = H.result_line(cell, ctx, out, metrics, [_Dev()])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["metrics"]["verdict_s"] == {"value": 1.5, "unit": "s"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["gap"] == {"value": 1e-7, "limit": 1e-5}
    json.dumps(line)
    out.checks.append(H.Check("bad", 2.0, 1.0))
    assert H.result_line(cell, ctx, out, metrics, [_Dev()])["correct"] \
        is False


def test_traced_result_carries_busy_window_and_breakdown(tmp_path):
    cell = H.resolve_cell("pod16-slowchip")
    ctx = H.Context(cell, 1, 1.0, True, tmp_path, {"bf16_flops_per_s": 1},
                    0.0)
    ctx.trace_summary = {"busy_s": 0.25, "window_s": 10.0,
                         "idle_share": 0.975,
                         "device_ops": [["fusion", 0.2]] * 12,
                         "idle_gaps": [["detect", 5.0]]}
    out = _outcome()
    out.extra["counters"] = {"verdicts": 4, "compiles": 0}
    metrics = H.metric_values(cell, ctx, out)
    assert metrics["device_idle_share.pod"]["value"] == pytest.approx(97.5)
    assert metrics["compiles.pod"]["value"] == 0
    assert "verdict_s" not in metrics
    line = H.result_line(cell, ctx, out, metrics, [_Dev()])
    assert line["device"]["busy_s"] == 0.25
    assert len(line["breakdown"]["device_ops"]) == 10
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", ["smollm135m-train-plain",
                                  "smollm135m-train-monitored"])
def test_traced_training_result_reads_its_layers(tmp_path, name):
    cell = H.resolve_cell(name)
    ctx = H.Context(cell, 1, 1.0, True, tmp_path, {"bf16_flops_per_s": 197e12},
                    0.0)
    ctx.trace_summary = {"busy_s": 9.0, "window_s": 10.0, "idle_share": 0.1,
                         "device_ops": [], "idle_gaps": []}
    out = H.Outcome(end_to_end={}, checks=[H.Check("loss_gap", 0.0, 1e-3)],
                    attempted=100, failed=0, window=(0.0, 10.0),
                    extra={"counters": {"steps": 100, "window_s": 10.0,
                                        "compiles": 0}})
    ctx.rec.spans["telemetry"] = [(1.0, 1.07), (2.0, 2.09)]
    metrics = H.metric_values(cell, ctx, out)
    assert metrics["compiles.train"]["value"] == 0
    assert metrics["device_idle_share.train"]["value"] == pytest.approx(10.0)
    assert 0 < metrics["train_mfu"]["value"] < 100
    assert ("telemetry_ms_per_window" in metrics) == (
        name.endswith("monitored"))


def _bench_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_a_run_without_a_tpu_fails_and_prints_no_result(tmp_path):
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "pod16-healthy", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pod16-slowchip",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_bench_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_files_are_found_by_name(tmp_path):
    """A cell, a configuration, a traffic mix and a per-layer metric added
    as new files plus entries are found without editing a file."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = H.load_benchmark()
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "configs" / "pod8.json").write_text(json.dumps(
        dict(json.loads((BENCH / "configs" / "pod16.json").read_text()),
             mesh_w=8, mesh_h=8)))
    (bench_dir / "traffic" / "slowlink.json").write_text(json.dumps(
        dict(json.loads((BENCH / "traffic" / "slowchip.json").read_text()),
             failure={"kind": "link", "location": 7, "slowdown": 10.0})))
    (bench_dir / "limits" / "pod8-slowlink.json").write_text(
        (BENCH / "limits" / "pod16-slowchip.json").read_text())
    (bench_dir / "layers" / "verdicts.pod.py").write_text(textwrap.dedent(
        '''
        def read(run):
            return run.counter("verdicts")
        '''))
    bench["configs"].append({"name": "pod8", "source": "x",
                             "file": "bench/configs/pod8.json", "reduced": [],
                             "why": "x"})
    bench["workloads"].append({"name": "pod8-slowlink", "config": "pod8",
                               "traffic": "slowlink", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "verdict_s":
            m["workloads"].append("pod8-slowlink")
    bench["per_layer"].append({"name": "verdicts.pod", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "detector", "moves": "verdict_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = H.resolve_cell("pod8-slowlink", bench_dir)
    assert cell.config["mesh_w"] == 8
    assert cell.traffic["failure"]["kind"] == "link"
    assert cell.limits["theta_gap"] > 0
    assert cell.check("theta_gap", 0.0).ok
    assert "verdicts.pod" in [m["name"] for m in cell.per_layer]
    assert H.driver_for(cell).__name__ == "bench_pod"
    out = _outcome()
    out.extra["counters"] = {"verdicts": 4}
    run = H.Run(cell, H.Recorder(), out, None, None)
    assert H.reader_for(cell, "verdicts.pod").read(run) == 4
    # the metric without a workloads list reaches every cell that reports
    # verdict_s, and no training cell
    assert "verdicts.pod" in [m["name"] for m in
                              H.resolve_cell("pod16-healthy",
                                             bench_dir).per_layer]
    assert "verdicts.pod" not in [
        m["name"] for m in H.resolve_cell("smollm135m-train-plain",
                                          bench_dir).per_layer]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_cell_and_missing_file_are_errors(tmp_path):
    with pytest.raises(H.BenchError):
        H.resolve_cell("no-such-cell")
    with pytest.raises(H.BenchError):
        H.reader_for(H.resolve_cell("pod16-slowchip"), "no_such_metric")
    with pytest.raises(H.BenchError):
        H.resolve_cell("pod16-slowchip").check("no_such_number", 0.0)


def test_percentile_interpolates_between_ranks():
    xs = list(range(1, 101))
    assert H.percentile(xs, 95) == pytest.approx(95.05)
    assert H.percentile([3.0], 95) == 3.0
