"""The benchmark's frame: finds a cell's files by the names in
``BENCHMARK.json``, checks the device, keeps the clock, the spans and the
compile count, runs the cell's driver, reads the per-layer metrics, and
prints the result line.

A cell is a configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``).  The mix names its driver
(``drivers/<driver>.py``); the cell's limits on the numbers that decide
``correct`` are in ``cells/<cell>.json``; each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric means adding files and entries; nothing here names one.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything one workload of ``BENCHMARK.json`` resolves to."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        bench_dir = root / "chipbench"
        work = {w["name"]: w for w in bench["workloads"]}
        if name not in work:
            raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
        self.name = name
        self.workload = work[name]
        self.chips = int(self.workload["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(root / conf["file"])
        self.traffic = load_json(
            bench_dir / "traffic" / f"{self.workload['traffic']}.json")
        self.driver_path = bench_dir / "drivers" / f"{self.traffic['driver']}.py"
        self.limits = load_json(bench_dir / "cells" / f"{name}.json")["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [])
                          or ("workloads" not in m and m["moves"] in e2e)]
        self.metric_paths = {m["name"]: bench_dir / "metrics" / f"{m['name']}.py"
                             for m in self.per_layer}
        for p in [self.driver_path, *self.metric_paths.values()]:
            if not p.is_file():
                raise FileNotFoundError(p)

    def driver(self):
        return load_module(self.driver_path)

    def readers(self):
        return {n: load_module(p) for n, p in self.metric_paths.items()}


class Run:
    """What a driver gets: the cell's files, the run's arguments, and the
    clock, spans and counters the harness keeps.  ``t0`` is the process's
    start on the host clock (``time.perf_counter``)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t0: float, trace_dir: Path | None = None):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed, seconds,
                                                          trace)
        self.config, self.traffic, self.limits = (cell.config, cell.traffic,
                                                  cell.limits)
        self.t0 = t0
        self.trace_dir = trace_dir
        self.window_t0 = None
        self.checks: list[tuple[str, float, float]] = []
        self.notes: dict = {}
        self._compiles = 0
        self._compiles_at_window = None
        self.memory_peak_bytes = None
        self.wrap = lambda step: step   # fault checks break the timed step

    # -- compile count
    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    # -- the window
    def start_window(self) -> float:
        """Called by the driver once set-up is done: ends ``setup_s``."""
        self._gc = []
        gc.callbacks.append(self._on_gc)
        self.window_t0 = time.perf_counter()
        self._compiles_at_window = self._compiles
        return self.window_t0

    def end_window(self):
        gc.callbacks.remove(self._on_gc)
        self.notes["compiles_in_window"] = (self._compiles
                                            - self._compiles_at_window)
        pauses = [t for _, t in self._gc]
        self.notes["gc_in_window"] = {"n": len(pauses), "s": sum(pauses),
                                      "max_s": max(pauses, default=0.0)}

    def _on_gc(self, phase, info):
        """Time the interpreter's garbage collections inside the window."""
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self._gc.append((info["generation"],
                             time.perf_counter() - self._gc_t))

    @property
    def setup_s(self) -> float:
        return self.window_t0 - self.t0

    def span(self, name: str):
        """A host span on the profiler's clock (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def traced(self):
        """Record a device trace of what runs inside, under one host span
        ``chipbench.traced`` that bounds the traced window."""
        import jax
        jax.profiler.start_trace(str(self.trace_dir))
        try:
            with jax.profiler.TraceAnnotation("chipbench.traced"):
                yield
        finally:
            jax.profiler.stop_trace()

    def read_memory_peak(self, devices):
        """Peak bytes on the fullest device; read once the window has closed
        and before the reference runs.  The TPU runtime keeps the
        temporaries of the programs it has loaded in a reserved region
        apart from the arrays it counts as in use, so the peak is the two
        peaks added.  The CPU backend keeps no statistics."""
        stats = [d.memory_stats() for d in devices]
        if all(stats):
            self.memory_peak_bytes = max(
                s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
                for s in stats)
            self.notes["memory_stats"] = stats[0]
        return self.memory_peak_bytes

    def check(self, name: str, value: float, limit: float | None = None):
        """Record one number compared with its limit (``value <= limit``);
        the limit comes from the cell's file unless given."""
        lim = self.limits[name] if limit is None else limit
        self.checks.append((name, float(value), float(lim)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v <= lim for _, v, lim in self.checks)


def result_line(run: Run, out: dict, device: dict, metrics: dict,
                breakdown: dict | None) -> str:
    line = {"correct": run.correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in run.checks}
    return json.dumps(line)


def print_checks(run: Run):
    for n, v, lim in run.checks:
        ok = "ok" if v <= lim else "FAIL"
        print(f"check {n} {v!r} limit {lim!r} {ok}", file=sys.stderr)
    print(f"correct {run.correct}", file=sys.stderr, flush=True)
