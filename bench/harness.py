"""The benchmark harness: finds a cell's files, checks the chip, runs the
cell's driver, reads its metrics and prints the result line.

Everything that belongs to one cell is found by name:

    BENCHMARK.json             the cells, metrics and bounds
    <config "file">            a configuration (bench/configs/<name>.json)
    bench/traffic/<name>.json  a traffic mix; its "kind" names the driver
    bench/drivers/<kind>.py    the driver of that traffic kind
    bench/metrics/<name>.py    one per-layer metric: ``read(readings)``
    bench/peaks.json           the chip's peaks, keyed by ``device_kind``

so a later change adds a configuration, a mix or a metric by adding files.

A driver module provides

    setup(cell, seed, system=None) -> state   data from the seed, warm-up
    window(state, seconds, spans, tracer) -> Window
    end_to_end(state, window) -> {metric name: value}
    release(state)                            drop the program's state
    check(state, window) -> [Check]           the comparison with the reference

``system`` replaces the program under test (the control, the fault tests).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# JAX's persistent compilation cache: a fixed directory inside the checkout,
# so the second run of a cell finds every program the first one compiled.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, fewer chips than the cell asks for, or a chip that
    the peaks table does not list."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window leaves for the metrics."""

    attempted: int
    failed: int
    data: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Readings:
    """Everything a per-layer metric reader may look at."""

    cell: Cell
    peaks: dict
    spans: "Spans"
    window: Window
    trace: Any = None  # xplane.Summary of a --trace 1 run, else None


# ----------------------------------------------------------------- discovery
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    w = next((w for w in spec["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()
    )
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)
    ]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer,
    )


def load_driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")


def load_metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise NoChip(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table["devices"][device_kind]


# -------------------------------------------------------------------- spans
class Spans:
    """Host spans of the harness's own calls into the program's layers,
    kept in memory; with a trace running they also go into the profiler
    as ``TraceAnnotation``s so the trace can attribute device time."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.records: dict[str, list[tuple[float, float]]] = {}
        self.annotate = None  # jax.profiler.TraceAnnotation while tracing

    @contextlib.contextmanager
    def span(self, name: str):
        ann = self.annotate(name) if self.annotate else contextlib.nullcontext()
        with ann:
            t0 = self.clock()
            try:
                yield
            finally:
                self.records.setdefault(name, []).append((t0, self.clock()))


@contextlib.contextmanager
def phase(name: str):
    """Print how long a phase of set-up took (standard error)."""
    t0 = time.perf_counter()
    yield
    print(f"setup: {name} {time.perf_counter() - t0:.3f} s", file=sys.stderr)


# ------------------------------------------------------------------ the chip
def configure_jax():
    """Import JAX with the persistent compilation cache in the checkout and
    the program's sources on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu writes its logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def chip_devices(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache)."""

    def __init__(self):
        self.count = 0
        self.names: list[str] = []

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def install(self) -> "CompileCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


# ------------------------------------------------------------------- result
def read_per_layer(readings: Readings) -> dict:
    out = {}
    for m in readings.cell.per_layer:
        value = load_metric(m["name"]).read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list[Check],
                breakdown: dict | None = None,
                extra: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(extra or {})
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out, allow_nan=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, peaks: dict | None = None,
             system=None, out=sys.stdout, err=sys.stderr) -> dict:
    """Set up, measure, release and check one cell; print the result line.

    ``devices``/``peaks`` are None off the chip (the rehearsal tests), where
    no device block, memory reading or trace is taken.
    """
    driver = load_driver(cell)
    compiles = CompileCounter().install()
    state = driver.setup(cell, seed, system=system)
    setup_s = time.perf_counter() - t_start

    spans = Spans()
    tracer = None
    if trace:
        from bench import xplane

        tracer = xplane.Tracer(TRACE_DIR / cell.name, spans)
    before = compiles.count
    win = driver.window(state, seconds, spans, tracer)
    win.data["window_compiles"] = compiles.count - before
    in_window = compiles.names[before:]
    peak = memory_peak_bytes(devices[:cell.chips]) if devices else None
    summary = tracer.summary() if tracer else None
    e2e = driver.end_to_end(state, win)
    driver.release(state)
    checks = driver.check(state, win)

    if trace:
        metrics = read_per_layer(Readings(cell, peaks or {}, spans, win,
                                          summary))
    else:
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    device = {}
    if devices:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        if summary is not None:
            device["busy_s"] = summary.busy_s()
            device["window_s"] = summary.window_s()
    correct = all(c.ok for c in checks) and win.failed == 0
    print(f"correct: {correct}; attempted {win.attempted}, failed "
          f"{win.failed}; compiles in window {win.data['window_compiles']}"
          f" {in_window[:8]}", file=err)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=err, flush=True)
    line = result_line(
        correct=correct, attempted=win.attempted, failed=win.failed,
        metrics=metrics, device=device, checks=checks,
        breakdown=summary.breakdown() if summary is not None else None,
        extra={"window_compiles": win.data["window_compiles"]},
    )
    print(line, file=out, flush=True)
    return json.loads(line)


def main(argv=None, *, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(load_spec(), args.workload)
    jax = configure_jax()
    try:
        devices = chip_devices(jax, cell.chips)
        peaks = load_peaks(devices[0].device_kind)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    run_cell(cell, seed=args.seed, seconds=args.seconds,
             trace=bool(args.trace), t_start=t_start,
             devices=devices, peaks=peaks)
    return 0
