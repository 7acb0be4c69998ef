"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything specific to a configuration, a traffic mix, a metric or a
cell is a file found by name, so a later change adds a cell with new
files and new entries alone:

* ``BENCHMARK.json``: the cells, and which metrics each reports;
* ``bench/configs/<config>.json``: the file a configuration entry names;
* ``bench/traffic/<mix>.json``: the parameters ``bench/generator.py``
  reads;
* ``bench/limits/<cell>.json``: the limit of each number the comparison
  with the reference holds the cell to;
* ``bench/metrics/<metric>.py``: one reader per metric, end-to-end and
  per-layer alike, with ``read(run) -> float | None`` (a name with a
  dot falls back to the file of its first part, ``reader``). A reader
  that finds nothing to read returns None and the metric is left out.

A ``--trace 0`` run reports the cell's end-to-end metrics, a
``--trace 1`` run its per-layer metrics: the whole window is traced by
the profiler, the trace is reduced by ``bench/tracing.py`` and deleted.

A cell's ``chips`` (1 or 4) is handed to ``generator.drive``, whose
closed loop then makes its inputs split over that many devices and runs
the program's sharded route on them (``bench/generator.py``); the
memory peak is the fullest of those devices, and the readers take the
count from ``run.cell.chips``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Optional

from bench import check, generator, roofline, tracing


def load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


@dataclasses.dataclass
class Cell:
    """A cell of the manifest with the files it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def find_cell(root: pathlib.Path, name: str) -> Cell:
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json"),
                limits=check.load_limits(root / "bench" / "limits"
                                         / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


@functools.lru_cache(maxsize=None)
def reader(root: pathlib.Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``, or, where
    there is none, of the file named by the metric's name up to its
    first dot: ``engine_roofline.solve`` and ``engine_roofline.serve``
    are one quantity read by ``engine_roofline.py``, split by the
    end-to-end metric each moves."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{metric}.py"
    if not path.is_file():
        path = metrics / f"{metric.split('.')[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} in "
                                f"{metrics}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    cell: Cell
    window: generator.Window
    setup_s: float
    device_kind: str
    trace: Optional[tracing.Summary] = None

    @functools.cached_property
    def count(self) -> dict:
        """The benchmark's operations, bytes and least time over the
        window's completed work (``bench/roofline.py``)."""
        tot = {"ops": 0.0, "bytes": 0.0, "roofline_s": 0.0}
        for w in self.window.work:
            one = roofline.work(self.cell.config, w["grid"], w["n_steps"],
                                self.device_kind)
            for k in tot:
                tot[k] += w["count"] * one[k]
        return tot


# JAX's monitoring events: a program compiled or read back from the
# persistent compilation cache (either way a call to the backend), and
# the cache's hits and misses (a miss is a program compiled anew).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "compiled"}


class _Hooks:
    """Marks the end of set-up, counts compiles in set-up and inside the
    window, and traces the window when asked."""

    def __init__(self, t_start: float, trace: bool):
        import jax
        self.t_start = t_start
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace \
            else None
        self.setup_s = None
        self.counts = {ph: {"compiles": 0, "cache_hits": 0, "compiled": 0}
                       for ph in ("setup", "window")}
        self._phase = "setup"
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw):
        if event == COMPILE_EVENT and self._phase:
            self.counts[self._phase]["compiles"] += 1

    def _on_event(self, event: str, **kw):
        if event in CACHE_EVENTS and self._phase:
            self.counts[self._phase][CACHE_EVENTS[event]] += 1

    def begin(self):
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        self._phase = "window"
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)

    def end(self):
        import jax
        self._phase = None
        if self.trace_dir:
            jax.profiler.stop_trace()

    def close(self):
        import jax
        self._phase = None
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def warm(root: pathlib.Path, name: str, seed: int) -> None:
    """A cell's set-up and one request or solve, nothing measured: the
    tuning and compiling of a checkout's first run."""
    cell = find_cell(pathlib.Path(root), name)
    generator.drive(cell.config, cell.traffic, seed, 0.0, annotate=False,
                    hooks=generator.NoHooks(), chips=cell.chips)


def run_cell(root: pathlib.Path, name: str, seed: int, seconds: float,
             trace: bool, t_start: float) -> tuple[dict, dict]:
    """Run a cell once: (result line, plan line). No check for a chip
    here: ``bench/run.py`` makes it before calling."""
    import jax
    root = pathlib.Path(root)
    cell = find_cell(root, name)
    init_s = time.perf_counter() - t_start
    hooks = _Hooks(t_start, trace)
    try:
        win = generator.drive(cell.config, cell.traffic, seed, seconds,
                              annotate=trace, hooks=hooks,
                              chips=cell.chips)
        devices = jax.devices()[: cell.chips]
        memory_peak = memory_peak_bytes(devices)
        summary = (tracing.reduce(tracing.load(hooks.trace_dir))
                   if trace else None)
    finally:
        hooks.close()
    correct, checks = check.judge(check.compare(win, cell.config),
                                  cell.limits)
    run = Run(cell=cell, window=win, setup_s=hooks.setup_s,
              device_kind=devices[0].device_kind, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    plan_line = {"cell": name, "seed": seed, "plan": win.plan,
                 "setup_s": hooks.setup_s, "init_s": init_s,
                 "window_s": win.seconds,
                 "compiles_in_window": hooks.counts["window"]["compiles"],
                 "setup_compiles": hooks.counts["setup"],
                 "counters": win.counters, **win.diagnostics}
    return result, plan_line
