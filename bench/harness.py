"""The benchmark's harness: it finds a cell's files by name, runs the
cell's set-up, its measured window and its check, and prints the result.

Everything that belongs to one cell lives in files of its own, found
through ``BENCHMARK.json``:

* ``workloads[].config`` -> the configuration's ``file`` (JSON);
* ``workloads[].traffic`` -> ``bench/traffic/<traffic>.json``, whose
  ``kind`` names the driver ``bench/drivers/<kind>.py``;
* each metric -> ``bench/metrics/<name>.py``, a reader with
  ``read(run) -> float | None`` (None: nothing to read, left out).

A driver is a class ``Driver(cell, seed, mesh, workdir, run)`` with
``setup()``, ``window(deadline)``, ``check()`` and ``close()``; ``check``
returns ``(checks, attempted, failed)`` where ``checks`` maps a short
name to ``(value, limit)`` and is correct when every value is at most
its limit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(ROOT, ".bench_work")  # index directories and traces


class BenchError(Exception):
    """A cell that cannot run as its files describe."""


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A module from a file whose name may hold dots (``a.b.py``)."""
    name = "bench_dyn_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, root: str = ROOT):
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise BenchError(f"no workload named {name!r}")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config = _load_json(
            os.path.join(root, configs[self.workload["config"]]["file"]))
        self.traffic = _load_json(os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"))

    def metrics(self, traced: bool) -> List[dict]:
        """The metrics this cell reports: its end-to-end ones untraced, its
        per-layer ones traced."""
        e2e = [m for m in self.spec["end_to_end"] if self._has(m)]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if self._has(m) and ("workloads" in m or m["moves"] in moved)]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])


@dataclass
class Run:
    """What a run measured; the metric readers take their numbers from it."""

    cell: str
    setup_s: float = 0.0
    setup: Dict[str, float] = field(default_factory=dict)
    window_s: float = 0.0
    jobs: List[dict] = field(default_factory=list)  # build jobs
    latencies_s: List[float] = field(default_factory=list)  # lookup batches
    queries: int = 0
    engine: Dict[str, int] = field(default_factory=dict)  # counter deltas
    tracing: bool = False
    kernel_calls: List[tuple] = field(default_factory=list)  # (name, shape)
    trace: Any = None  # trace.Summary of the traced window
    device_kind: str = ""


class Meter:
    """JAX's own compile and compile-cache events, and the host's garbage
    collections, counted since start."""

    def __init__(self):
        import gc

        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.gc_s = 0.0
        self.gcs = 0
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gcs += 1
            self._gc_t0 = None

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += secs
            if event.endswith("backend_compile_duration"):
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def configure_jax() -> None:
    """The persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``; every program is
    kept, so a run after the first compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def tpu_devices(chips: int) -> list:
    """The cell's chips; raises when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def _latency_profile(latencies_s: List[float], slice_s: float = 10.0):
    """Batches answered in each ``slice_s`` of the window and the latency
    quantiles, for a log line: where in a window a slow run lost time."""
    if not latencies_s:
        return {}
    import numpy as np

    lat = np.asarray(latencies_s)
    done = np.cumsum(lat)  # closed loop: each batch starts as one ends
    per = np.bincount((done // slice_s).astype(int)).tolist()
    q = np.percentile(lat, [50, 95, 100]) * 1e3
    return {"batches_per_slice": per, "slice_s": slice_s,
            "latency_ms_p50_p95_max": q.tolist()}


def _job_profile(jobs: List[dict]):
    """Each build's wall time and the program's phase timers (``t_*_s``),
    for a log line: which phase of which build a slow run lost time in."""
    if not jobs:
        return {}
    return {"job_s": [{"wall_s": j["wall_s"],
                       **{k: v for k, v in j["stats"].items()
                          if k.startswith("t_") and k.endswith("_s")}}
                      for j in jobs]}


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans and runtime events only
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices: list, t_start: float, meter: Meter,
             log=print) -> dict:
    """Set-up, window, check and (traced) trace reduction of one run;
    returns the result line's object."""
    import numpy as np
    from jax.sharding import Mesh

    from bench import trace as trace_mod
    from repro.core.pipeline import AXIS  # the mesh axis the program shards

    run = Run(cell=cell.name, tracing=traced,
              device_kind=devices[0].device_kind)
    run.setup["import_s"] = time.perf_counter() - t_start
    driver_cls = load_module(os.path.join(
        BENCH, "drivers", cell.traffic["kind"] + ".py")).Driver
    readers = {m["name"]: load_module(os.path.join(
        BENCH, "metrics", m["name"] + ".py")) for m in cell.metrics(traced)}
    os.makedirs(WORK, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run_", dir=WORK)
    try:
        mesh = Mesh(np.array(devices), (AXIS,))
        driver = driver_cls(cell, seed, mesh, workdir, run)
        try:
            run.setup.update(driver.setup())
            run.setup["compile_s"] = meter.compile_s
            run.setup["compiles"] = meter.compiles
            run.setup["cache_hits"] = meter.cache_hits
            run.setup_s = time.perf_counter() - t_start
            log(json.dumps({"setup_s": run.setup_s, **run.setup}))
            tdir = os.path.join(workdir, "trace")
            if traced:
                import jax

                jax.profiler.start_trace(tdir,
                                         profiler_options=_profile_options())
            c0, n0 = meter.compile_s, meter.compiles
            g0, gn0 = meter.gc_s, meter.gcs
            t0 = time.perf_counter()
            with span("window"):
                driver.window(t0 + seconds)
            run.window_s = time.perf_counter() - t0
            log(json.dumps({"window_s": run.window_s,
                            "compiles_in_window": meter.compiles - n0,
                            "compile_s_in_window": meter.compile_s - c0,
                            "gc_in_window": meter.gcs - gn0,
                            "gc_s_in_window": meter.gc_s - g0,
                            **_job_profile(run.jobs),
                            **_latency_profile(run.latencies_s)}))
            if traced:
                jax.profiler.stop_trace()
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
            with span("check"):
                checks, attempted, failed = driver.check()
        finally:
            driver.close()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        out: Dict[str, Any] = {}
        if traced:
            run.trace = trace_mod.reduce(trace_mod.find_xplane(tdir),
                                         len(devices))
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            out["breakdown"] = run.trace.breakdown()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    metrics = {}
    for m in cell.metrics(traced):
        v = readers[m["name"]].read(run)
        if v is None:
            if m in cell.spec["end_to_end"]:
                raise BenchError(f"end-to-end metric {m['name']} read "
                                 "nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct = attempted > 0 and all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **out,
              "checks": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}}
    return result


def emit(result: dict) -> None:
    """The result: each compared number beside its limit as the last lines
    of standard error, and the JSON object as the last line of standard
    output."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[list] = None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    try:
        cell = Cell(args.workload)
        configure_jax()
        meter = Meter()
        devices = tpu_devices(cell.chips)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, t_start, meter,
                          log=lambda s: print(s, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0
