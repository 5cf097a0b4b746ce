"""The benchmark's general part: it finds a cell's files by the names in
BENCHMARK.json, runs the cell's runner, reads the per-layer metrics and
prints the result. Nothing here knows a configuration, a traffic mix or a
metric by name; a new cell, mix, runner or metric is new files and new
BENCHMARK.json entries.

A runner module (``bench/runners/<runner>.py``) has ``run(ctx) -> Outcome``.
It loads and warms up, calls ``ctx.window()`` around the measured loop,
calls ``ctx.read_memory_peak()`` right after it, frees the program's state,
and then checks what the timed path produced against the reference. A
per-layer metric module (``bench/metrics/<metric>.py``) has
``read(record) -> float | None``, where ``record`` is the runner's
``Outcome.record`` plus ``trace`` (a ``bench.trace.Trace``), ``window_s``,
``chips`` and ``peak`` (the device's row of ``peaks.json``); ``None``
leaves the metric out of the line.

Adding a cell. A new cell brings, as new files:

* its configuration, ``bench/configs/<config>.json``: the sizes as run,
  ``registry`` (the program's registry entry it starts from) and
  ``program``, which maps each field of the program's ``ModelConfig`` that
  the file changes to the key of the file that holds its value (see
  ``model_config``; a file that changes nothing has no ``program``). Beside
  it the counts ``bench/configs/<config>.py`` and the plain reference
  ``bench/reference/<config>.py``, which read the same keys. It may hold a
  ``tiny`` object, the CPU cut of its sizes;
* its traffic mix, ``bench/traffic/<traffic>.json``, whose ``runner`` names
  the runner, and which holds a ``tiny`` object: a partial copy of the
  file, deep-merged over it for the CPU tests (``bench/tests/bench_tiny.py``),
  which may only cut keys the file has. Runners never read ``tiny``;
* where no runner or metric reader fits, ``bench/runners/<runner>.py`` and
  ``bench/metrics/<metric>.py``;

and, in BENCHMARK.json, its ``configs`` and ``workloads`` entries, its name
appended to the ``workloads`` list of every end-to-end metric it reports
(``setup_s`` has none: every cell reports it) and of every per-layer metric
it should report. The CPU tests then run the cell at its tiny cut, its
control and its planted faults (``bench/calibrate.py``) with no edit to an
existing file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one of the benchmark's files by path (names may hold ``-``
    and ``.``, so they are not importable by name)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's ``workloads``, with its files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the end-to-end metrics this cell reports
    per_layer: List[dict]       # the per-layer metrics this cell reports

    @classmethod
    def load(cls, name: str, spec: Optional[dict] = None) -> "Cell":
        spec = spec or read_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        e2e = [m for m in spec["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in spec["per_layer"]
                     if (name in m["workloads"] if "workloads" in m
                         else m["moves"] in reported)]
        return cls(name=name, chips=int(w["chips"]),
                   config=read_json(BENCH / "configs" / f"{w['config']}.json"),
                   traffic=read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                   end_to_end=e2e, per_layer=per_layer)

    @property
    def counts(self):
        return load_module(BENCH / "configs" / f"{self.config['name']}.py")

    @property
    def reference(self):
        return load_module(BENCH / "reference" / f"{self.config['name']}.py")

    @property
    def runner(self):
        return load_module(BENCH / "runners" / f"{self.traffic['runner']}.py")


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry the file names, with each field that the file's ``program`` maps
    to one of its keys set from that key (a list as a tuple). A file that
    states no change runs the registry's entry as it is."""
    from repro.configs import get_config
    base = get_config(config["registry"])
    fields = {f.name for f in dataclasses.fields(base)}
    changes = {}
    for field, key in config.get("program", {}).items():
        if field not in fields:
            raise KeyError(f"configuration {config['name']!r}: the program's "
                           f"ModelConfig has no field {field!r}")
        value = config[key]
        changes[field] = tuple(value) if isinstance(value, list) else value
    return dataclasses.replace(base, **changes)


def require_chips(n: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")


class CompileClock:
    """Sums JAX's trace, lowering and compile durations, and counts traces
    (a trace inside the window means something compiled there)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[0]:
            self.traces += 1


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a runner hands back."""

    metrics: Dict[str, float]       # end-to-end values, setup_s excepted
    attempted: int
    failed: int
    checks: List[Check]
    record: dict                    # what the per-layer readers read


class Context:
    """What a runner gets: its cell, seed and window, and the harness's
    clock, spans, profiler window and memory reading."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t0: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = trace
        self.t0 = t0
        self.clock = CompileClock()
        self.window_start: Optional[float] = None
        self.window_s: Optional[float] = None
        self.window_traces = 0
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir: Optional[str] = None
        self.span_names = {"window"}
        self.control = False        # calibration only: the reference in
                                    # one bfloat16 pass stands in for the
                                    # program's output

    @staticmethod
    def emit(**line) -> None:
        """An earlier line of stdout (set-up readings, counts)."""
        print(json.dumps(line), flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """A set-up phase: its seconds and compile seconds on an earlier
        line."""
        t0, c0 = time.perf_counter(), self.clock.seconds
        yield
        self.emit(phase=name, seconds=time.perf_counter() - t0,
                  compile_s=self.clock.seconds - c0)

    def span(self, name: str):
        """A host span in the profiler's trace (no cost when not tracing)."""
        import jax
        self.span_names.add(name)
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts, and with
        ``--trace 1`` the profiler records it."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # the harness's spans suffice
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        traces = self.clock.traces
        self.window_start = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - self.window_start
            self.window_traces = self.clock.traces - traces
            if self.trace:
                jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.cell.chips]]
        self.memory_peak_bytes = int(max(peaks))


def _device(ctx: Context) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "memory_peak_bytes": ctx.memory_peak_bytes}


def configure(cell: Cell, cache: bool = True) -> None:
    """JAX as the cell runs: the persistent compilation cache at a fixed
    path inside the checkout (``.jax_cache``, where the program's own
    ``use_compile_cache`` puts it; an inherited JAX_COMPILATION_CACHE_DIR
    is not used, so two checkouts never share a cache), and the matrix
    precision the traffic file states."""
    import jax
    if cache:
        (ROOT / ".jax_cache").mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        # every program goes to the persistent cache, however quickly it
        # compiled, so that only a cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    prec = cell.traffic.get("matmul_precision", "default")
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """Run the cell once; the result line as a dict (``checks`` last)."""
    configure(cell)
    ctx = Context(cell, seed, seconds, trace, t0)
    out = cell.runner.run(ctx)
    if ctx.window_start is None or ctx.memory_peak_bytes is None:
        raise RuntimeError("the runner measured no window")
    ctx.emit(setup_compile_s=ctx.clock.seconds, window_traces=ctx.window_traces,
             window_s=ctx.window_s)
    values = dict(out.metrics, setup_s=ctx.window_start - t0)
    device = _device(ctx)
    line = {"correct": bool(out.checks) and all(c.ok for c in out.checks)
            and out.failed == 0 and out.attempted > 0,
            "attempted": out.attempted, "failed": out.failed}
    if trace:
        from bench import trace as tr
        peaks = read_json(BENCH / "peaks.json")["devices"]
        if device["kind"] not in peaks:
            raise RuntimeError(f"no peaks for device kind {device['kind']!r}"
                               " in bench/peaks.json")
        try:
            t = tr.Trace.load(ctx.trace_dir, chips=cell.chips,
                              span_names=ctx.span_names)
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        record = dict(out.record, trace=t, window_s=ctx.window_s,
                      chips=cell.chips, peak=peaks[device["kind"]])
        metrics = {}
        for m in cell.per_layer:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=t.busy_s, window_s=t.window_s)
        line.update(metrics=metrics, device=device,
                    breakdown=t.breakdown())
    else:
        line.update(metrics={m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end},
                    device=device)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_result(line: dict) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, c in line["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
