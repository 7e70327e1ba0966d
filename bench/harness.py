"""The benchmark harness: finds a cell by name, checks the chip, runs the
cell's driver, reads the per-layer metrics and prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in BENCHMARK.json:

  configs[i].file            the configuration's sizes (JSON);
  traffic/<traffic>.json     the traffic mix; its "driver" names
                             drivers/<driver>.py, which runs it;
  layer_metrics/<metric>.py  a per-layer metric's reader, `read(run)`;
  work/<kernel>.py           a kernel's operations and bytes from shapes.

A driver's `run(ctx)` builds the deployment from the seed, warms up every
shape it will use, calls `ctx.open_window()` and measures (or, traced,
records one short window inside `ctx.traced()`), calls
`ctx.close_window()`, frees the program's state and runs the reference
checks. It returns a `DriverResult`.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, NamedTuple, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class Check(NamedTuple):
    name: str
    value: float
    limit: Any       # a number, or [low, high] for "in"
    op: str          # "<=", ">=" or "in"

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        if self.op == "in":
            return self.limit[0] <= self.value <= self.limit[1]
        return (self.value <= self.limit if self.op == "<="
                else self.value >= self.limit)


def checks_from(readings: dict, limits: dict) -> list[Check]:
    """One check per limit of the configuration, on the reading of the
    same name (nan where the run gave none, which fails)."""
    return [Check(name, readings.get(name, float("nan")), spec["limit"],
                  spec["op"]) for name, spec in limits.items()]


class DriverResult(NamedTuple):
    attempted: int
    failed: int
    end_to_end: dict[str, float]   # the cell's end-to-end metrics but setup_s
    counters: dict[str, Any]       # program counters for the layer readers
    checks: list[Check]
    notes: dict[str, Any]          # printed, not compared


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: Optional[str] = None) -> Cell:
    with open(bench_json or os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if applies(m, name)],
                [m for m in spec["per_layer"] if applies(m, name)])


def setup_jax(cache_dir: str = CACHE_DIR) -> None:
    """Persistent compilation cache at a fixed path in the checkout, every
    program cached, so only a cell's first run in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> list:
    import jax
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise NoChip(f"no TPU: {e}") from e
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU chips, the cell asks for {chips}")
    return devs[:chips]


def prng_key(seed: int):
    """A key from any whole seed: the low 32 bits seed it, the rest are
    folded in, so seeds past 2**32 stay distinct."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class Context:
    """What a driver gets: the cell, the seed, the window length, and the
    hooks that mark the window and record a trace."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: list, t_start: float, log: Callable[[str], None]):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.seed, self.seconds, self.tracing = seed, seconds, trace
        self.devices, self.t_start, self.log = devices, t_start, log
        self.window_open_at: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir: Optional[str] = None
        self.compiles_in_window = 0
        self.gc_full_s: list[float] = []
        self._gc_start = 0.0
        self.window_closed = False

    def span(self, name: str):
        """A host span in the trace when tracing, else nothing."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _on_event(self, event: str, *args, **kw) -> None:
        if "backend_compile" in event:
            self.compiles_in_window += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_full_s.append(time.perf_counter() - self._gc_start)

    def open_window(self) -> float:
        """Ends set-up with a full collection, so every run enters the
        window with the same collector state, and starts the window
        (counting compilations and full collections in it)."""
        import jax
        gc.collect()
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.window_open_at = time.perf_counter()
        return self.window_open_at

    def close_window(self) -> None:
        """Ends the measured window and reads the peak device memory,
        before the driver frees its state and runs the reference."""
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        gc.callbacks.remove(self._on_gc)
        self.window_closed = True
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        known = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(known) if known else None

    @contextlib.contextmanager
    def traced(self):
        """Record a profiler trace of the block, with the window span
        around it; the python tracer is off so host timing stays close to
        an untraced run."""
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()


class LayerRun(NamedTuple):
    """What a per-layer reader reads."""
    cell: Cell
    counters: dict
    summary: Any                 # trace.Summary of the traced window
    peaks: dict                  # the chip's published peaks
    work: Callable[[str], Any]   # kernel name -> work/<kernel>.py module


def read_layer_metrics(cell: Cell, counters: dict, summary, peaks: dict
                       ) -> dict:
    def work(kernel: str):
        return load_module(os.path.join(BENCH, "work", kernel + ".py"),
                           "work_" + kernel)

    run = LayerRun(cell, counters, summary, peaks, work)
    out = {}
    for m in cell.per_layer:
        reader = load_module(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".py"),
            "layer_" + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list,
             log: Callable[[str], None]) -> dict:
    """Run one cell on `devices` and return the result line's object."""
    import jax

    import peaks as peaks_table
    import xtrace as trace_mod

    ctx = Context(cell, seed, seconds, trace, devices, t_start, log)
    driver = load_module(
        os.path.join(BENCH, "drivers", cell.traffic["driver"] + ".py"),
        "driver_" + cell.traffic["driver"])
    res: DriverResult = driver.run(ctx)
    if ctx.window_open_at is None or not ctx.window_closed:
        raise RuntimeError("the driver never opened and closed its window")
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out: dict[str, Any] = {}
    if trace:
        tr = trace_mod.load(trace_mod.find_xplane(ctx.trace_dir))
        t0, t1 = trace_mod.window(tr, WINDOW_SPAN)
        summary = trace_mod.summarize(tr, t0, t1)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        metrics = read_layer_metrics(cell, res.counters, summary,
                                     peaks_table.peaks(dev.device_kind))
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9
        out["breakdown"] = trace_mod.breakdown(summary)
    else:
        metrics = {}
        values = dict(res.end_to_end,
                      setup_s=ctx.window_open_at - t_start)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    notes = dict(res.notes, compiles_in_window=ctx.compiles_in_window,
                 full_collections_s=ctx.gc_full_s, jax=jax.__version__)
    correct = res.failed == 0 and all(c.ok for c in res.checks)
    return {"correct": correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device,
            **out, "notes": notes,
            "checks": {c.name: {"value": c.value, "limit": c.limit,
                                "op": c.op, "ok": c.ok}
                       for c in res.checks}}


def check_lines(result: dict) -> list[str]:
    lines = [f"check {name}: {c['value']!r} (limit {c['op']} "
             f"{c['limit']!r}) {'ok' if c['ok'] else 'FAILED'}"
             for name, c in result["checks"].items()]
    lines.append(f"correct: {result['correct']} (failed {result['failed']}"
                 f" of {result['attempted']})")
    return lines


def main(args, t_start: float) -> int:
    def log(msg: str) -> None:
        print(f"[bench {time.perf_counter() - t_start:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    cell = load_cell(args.workload)
    setup_jax()
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        log(f"refused: {e}")
        return 3
    from repro.kernels import ops
    mode = ops.resolve_backend("auto")
    if mode != "pallas":
        log(f"refused: kernel backend resolves to {mode!r}, not 'pallas'")
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices, log)
    for line in check_lines(result):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
