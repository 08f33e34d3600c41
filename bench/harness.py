"""The benchmark's shared machinery: cells, files found by name, spans,
compile events, the measured window and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run.  Its
  ``driver`` key names ``bench/drivers/<driver>.py``, the general code
  that drives that kind of system.
* ``bench/traffic/<traffic>.json``: the parameters of one traffic mix,
  read by the driver's generator.
* ``bench/reference/<reference>.py``: the plain reference that a
  configuration names with its ``reference`` key, where its driver asks
  for one (``Cell.reference``).
* ``bench/layers/<metric>.py``: a reader with ``read(run) -> float |
  None`` that takes one per-layer metric from the run's spans, counters
  and trace.  ``None`` leaves the metric out of the result line.
* ``bench/limits/<cell>.json``: the limit of every number that the cell's
  correctness check compares (``limits``), beside the readings each was
  set from (``readings``).

A cell remembers the directory it was resolved from (``Cell.bench_dir``);
its driver, readers and reference are loaded from there alone.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """The run cannot be measured; it exits nonzero and prints no result."""


# ---------------------------------------------------------------------------
# cells and the files found by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]      # the end-to-end metrics this cell reports
    per_layer: list[dict]       # the per-layer metrics read in this cell
    limits: dict[str, float]    # the limit of each number compared
    bench_dir: Path             # where its driver, readers and reference are

    @functools.cached_property
    def reference(self):
        """The plain reference that the configuration names
        (``"reference": "<name>"``), ``bench/reference/<name>.py``,
        imported once per cell."""
        name = self.config.get("reference")
        if not name:
            raise BenchError(f"{self.bench_dir / 'configs'}/"
                             f"{self.config_name}.json names no reference")
        return load_module(self.bench_dir / "reference" / f"{name}.py")

    def check(self, name: str, value: float) -> "Check":
        """``value`` of the number ``name`` beside the cell's limit."""
        if name not in self.limits:
            raise BenchError(f"bench/limits/{self.name}.json has no limit "
                             f"for {name!r}")
        return Check(name, value, self.limits[name])


def reports(metric: dict, cell: str, e2e_of_cell: set[str]) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list where it
    has one, else every cell that reports the end-to-end metric it moves
    (or, for an end-to-end metric, every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def resolve_cell(name: str, bench_dir: Path = BENCH_DIR,
                 bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(bench_dir.parent)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"] if reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=read_json(bench_dir / "configs" / f"{w['config']}.json"),
                traffic=read_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer,
                limits=read_json(bench_dir / "limits"
                                 / f"{name}.json")["limits"],
                bench_dir=bench_dir)


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str | None = None):
    """Import one file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_for(cell: Cell):
    return load_module(cell.bench_dir / "drivers"
                       / f"{cell.config['driver']}.py")


def reader_for(cell: Cell, metric: str):
    return load_module(cell.bench_dir / "layers" / f"{metric}.py")


# ---------------------------------------------------------------------------
# spans and compile events
# ---------------------------------------------------------------------------

class Recorder:
    """Host spans around the program's calls and compile events, all on
    ``time.perf_counter``'s clock.

    ``wrap`` replaces a module or class attribute with a timed wrapper,
    which also writes a ``bench.<span>`` annotation into a profiler trace
    when one is running, so that the trace's idle gaps can be named.
    ``unwrap_all`` puts every original back.
    """

    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.compile_events: list[tuple[float, str]] = []
        self._undo: list[tuple[object, str, object]] = []
        self.hooks: dict[str, object] = {}   # span -> fn(args, kw, result)

    def span(self, name: str):
        return _Span(self, name)

    def wrap(self, owner, attr: str, span: str) -> None:
        orig = getattr(owner, attr)
        rec = self

        @functools.wraps(orig)
        def timed(*args, **kw):
            with rec.span(span):
                out = orig(*args, **kw)
            hook = rec.hooks.get(span)
            if hook is not None:
                hook(args, kw, out)
            return out

        self.replace(owner, attr, timed)

    def replace(self, owner, attr: str, new) -> None:
        """Set ``owner.attr`` to ``new`` until ``unwrap_all``."""
        self._undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    def unwrap_all(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)

    def within(self, name: str, t0: float, t1: float) -> list[float]:
        """Durations of ``name`` spans that start inside ``[t0, t1]``."""
        return [e - s for s, e in self.spans.get(name, ()) if t0 <= s <= t1]

    # -- jax.monitoring (the listener of chip_smoke.py) ---------------------
    def listen_compiles(self) -> None:
        """Note the time of every backend compile and compile-cache load."""
        import jax

        def on_duration(event: str, secs: float, **_kw) -> None:
            if event == _BACKEND_COMPILE:
                self.compile_events.append((time.perf_counter(), event))

        def on_event(event: str, **_kw) -> None:
            if event == _CACHE_HIT:
                self.compile_events.append((time.perf_counter(), event))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles_within(self, t0: float, t1: float) -> int:
        """Backend compiles and compile-cache loads inside ``[t0, t1]``."""
        return sum(1 for t, _ in self.compile_events if t0 <= t <= t1)


class _Span:
    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation("bench." + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.rec.spans.setdefault(self.name, []).append((self.t0, t1))
        return False


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared, beside its limit: ``ok`` iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    end_to_end: dict[str, float]
    checks: list[Check]
    attempted: int
    failed: int
    window: tuple[float, float]         # perf_counter bounds of the window
    extra: dict = dataclasses.field(default_factory=dict)


class Context:
    """What a driver gets: the cell, the seed, the window's length, the
    span recorder and the tracing switch."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 out_dir: Path, peak: dict | None, t_start: float):
        self.cell = cell
        self.t_start = t_start          # process start: set-up counts from it
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.out_dir = out_dir
        self.peak = peak
        self.rec = Recorder()
        self.trace_summary = None
        self.memory_peak_bytes = None
        self._trace_dir = out_dir / "trace"

    @contextlib.contextmanager
    def window(self):
        """The measured window: with ``--trace 1`` a profiler trace runs
        through it.  The device's peak bytes are read when it closes."""
        import jax
        if self.trace:
            from jax.profiler import ProfileOptions
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self._trace_dir),
                                     profiler_options=opts)
        try:
            with self.rec.span("window"):
                yield
        finally:
            if self.trace:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                print(f"bench: trace written in {time.perf_counter() - t0:.1f}"
                      " s", file=sys.stderr)
            self.memory_peak_bytes = peak_bytes()

    def reduce_trace(self) -> None:
        if not self.trace:
            return
        from devtrace import reduce_profile
        t0 = time.perf_counter()
        try:
            self.trace_summary = reduce_profile(self._trace_dir)
        except (FileNotFoundError, ValueError) as e:
            raise BenchError(f"trace: {e}") from e
        print(f"bench: trace reduced in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)


def peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def require_chips(n: int):
    """The accelerator devices, which must be TPUs, at least ``n``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r});"
                         " nothing was run")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def load_peak(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn with ``rng`` (Algorithm R): the same seed keeps the same items."""

    def __init__(self, k: int, rng):
        self.k, self.rng = k, rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer reader sees."""
    cell: Cell
    rec: Recorder
    outcome: Outcome
    trace: dict | None
    peak: dict | None

    @property
    def t0(self) -> float:
        return self.outcome.window[0]

    @property
    def t1(self) -> float:
        return self.outcome.window[1]

    def counter(self, name: str) -> float | None:
        return self.outcome.extra.get("counters", {}).get(name)


def metric_values(cell: Cell, ctx: Context, outcome: Outcome) -> dict:
    if not ctx.trace:
        out = {}
        for m in cell.end_to_end:
            v = outcome.end_to_end.get(m["name"])
            if v is None:
                raise BenchError(f"driver gave no {m['name']}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    run = Run(cell, ctx.rec, outcome, ctx.trace_summary, ctx.peak)
    out = {}
    for m in cell.per_layer:
        v = reader_for(cell, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(cell: Cell, ctx: Context, outcome: Outcome,
                metrics: dict, devices) -> dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": all(c.ok for c in outcome.checks) and bool(
                outcome.checks),
            "attempted": outcome.attempted, "failed": outcome.failed,
            "metrics": metrics, "device": device}
    if ctx.trace and ctx.trace_summary:
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_summary["window_s"]
        line["breakdown"] = {
            "device_ops": ctx.trace_summary["device_ops"][:10],
            "idle_gaps": ctx.trace_summary["idle_gaps"][:10]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def print_checks(checks: list[Check], stream=sys.stderr) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=stream)
    stream.flush()
