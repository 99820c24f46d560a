"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything that belongs to one cell, configuration, traffic driver or
per-layer metric is a file of its own, found by name:

* ``BENCHMARK.json``'s workload entry names the cell's configuration;
  the configuration entry names its file (``chipbench/configs/``);
* ``chipbench/cells/<cell>.json`` names the traffic driver, its
  parameters and the limit of every number ``compare.py`` reads;
* ``chipbench/traffic/<driver>.py`` builds the system under test, warms
  it up and drives the measured window (``prepare``/``measure``/
  ``release``);
* ``chipbench/metrics/<metric>.py`` reads one per-layer metric
  (``read(record)``, ``None`` when it finds nothing to read).

A run: make the data from the seed, build and warm the system (all of it
set-up), measure ``--seconds`` of traffic, read the device's peak memory,
free the system, compare a sample of the answers with the plain
reference, and print the metrics of the cell (end-to-end ones with
``--trace 0``, per-layer ones with ``--trace 1``) as the last line of
standard output.  Without an accelerator, or with fewer chips than the
cell asks for, it prints no result and exits with code 3.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
#: where a traced run keeps its profile (replaced by the next traced run)
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
NO_ACCELERATOR = 3


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: str
    chips: int
    driver: ModuleType
    params: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class Data:
    edges: object       # graphs.EdgeList, what the reference reads
    program: object     # the same graph as the program's host CSR


@dataclasses.dataclass
class Record:
    """What a per-layer metric reader gets."""
    cell: Cell
    counters: dict
    trace: Optional[object]     # trace.Summary, or None without a trace
    device_kind: str


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str) -> Cell:
    """The cell ``name`` with everything its files say."""
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cell_file = _json(os.path.join(HERE, "cells", f"{name}.json"))
    for key in ("config", "traffic"):
        if cell_file[key] != entry[key]:
            raise ValueError(f"cells/{name}.json says {key} "
                             f"{cell_file[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name=name, config_name=entry["config"],
                config=_json(os.path.join(ROOT, cfg_entry["file"])),
                traffic=entry["traffic"], chips=int(entry["chips"]),
                driver=importlib.import_module(
                    f"chipbench.traffic.{cell_file['driver']}"),
                params=cell_file["params"], limits=cell_file["limits"],
                end_to_end=e2e, per_layer=layer)


def metric_reader(name: str) -> ModuleType:
    """``chipbench/metrics/<name>.py`` (names may hold dots)."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def devices(chips: int):
    """The chips this run uses; no accelerator, or too few, is an error."""
    import jax
    found = jax.devices()
    if found[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator, only the CPU")
    if len(found) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(found)}")
    return found[:chips]


def enable_compile_cache():
    """JAX's persistent compilation cache at the checkout's fixed path
    (``$JAX_COMPILATION_CACHE_DIR`` where set), every program kept."""
    import jax
    from repro.launch.persistent_cache import enable_persistent_cache
    path = enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts requests for a compiled program (an XLA compile or a load
    from the persistent cache), the loads among them, and the seconds
    spent tracing and lowering, as JAX reports them."""

    def __init__(self):
        import jax
        self.compiles = self.loads = 0
        self.trace_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif name.startswith("/jax/core/compile/"):
            self.trace_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def snapshot(self):
        return self.compiles, self.loads, self.trace_s


class Tracer:
    """The profiler around the part of the window a driver chooses; a
    no-op in an untraced run.  ``span`` marks host work in the trace."""

    def __init__(self, on: bool):
        self.on = on
        self.active = False
        self._window = None
        #: seconds spent starting and stopping the profiler, which a
        #: driver leaves out of the times its counters are divided by
        self.overhead_s = 0.0

    def start(self):
        if self.on and not self.active and self._window is None:
            import jax
            t = time.perf_counter()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            from chipbench.trace import WINDOW_SPAN
            self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._window.__enter__()
            self.active = True
            self.overhead_s += time.perf_counter() - t

    def stop(self):
        if self.active:
            import jax
            t = time.perf_counter()
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
            self.overhead_s += time.perf_counter() - t

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def make_data(config: dict, seed: int) -> Data:
    from chipbench.graphs import make_graph, program_graph
    edges = make_graph(config, np.random.default_rng([seed, 0]))
    return Data(edges=edges, program=program_graph(edges))


def _peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(cell: Cell, seed: int, seconds: float, trace: bool, devs,
        t_start: float, log=print) -> dict:
    """One run of ``cell``; returns the result line's object."""
    from chipbench import compare
    from chipbench import trace as trace_mod
    counter = CompileCounter()
    data = make_data(cell.config, seed)
    system = cell.driver.prepare(cell, data, seed)
    setup_s = time.perf_counter() - t_start
    tracer = Tracer(trace)
    before = counter.snapshot()
    window = cell.driver.measure(system, seconds, tracer)
    tracer.stop()
    after = counter.snapshot()
    peak = _peak_bytes(devs)
    cell.driver.release(system)
    del system
    gc.collect()
    for note in window["notes"]:
        log(note)
    loads = after[1] - before[1]
    log(f"compiles in the window: {after[0] - before[0] - loads} XLA "
        f"compiles, {loads} programs loaded from the persistent cache, "
        f"{after[2] - before[2]:.3f} s tracing and lowering; set-up "
        f"{setup_s:.3f} s")
    readings = compare.numbers(data.edges, window["answers"],
                               alpha=float(cell.config.get("alpha", 0.15)),
                               eps=float(cell.config.get("eps", 1e-4)))
    correct = (compare.verdict(readings, cell.limits)
               and window["failed"] == 0)
    limits = dict(cell.limits, missing=0.0)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in sorted(readings.items())}
    paths = {}
    for a in window["answers"]:
        paths[a.path] = paths.get(a.path, 0) + 1
    log(f"compared {len(window['answers'])} answers by path {paths}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(window["attempted"]),
           "failed": int(window["failed"])}
    if not trace:
        values = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    else:
        summary = None
        path = trace_mod.latest_xplane(TRACE_DIR)
        if path is not None:
            summary = trace_mod.summarize(trace_mod.load(path))
        record = Record(cell=cell, counters=window["counters"],
                        trace=summary, device_kind=devs[0].device_kind)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = {"device_ops": summary.device_ops,
                                "idle_gaps": summary.idle_gaps}
    out["metrics"] = metrics
    out["device"] = device
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    def log(msg):
        print(f"[chipbench] {msg}", file=sys.stderr, flush=True)

    try:
        devs = devices(cell.chips)
    except NoAccelerator as e:
        log(f"no result: {e}")
        return NO_ACCELERATOR
    log(f"{cell.name}: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")
    out = run(cell, args.seed % (1 << 64), args.seconds, bool(args.trace),
              devs, t_start, log)
    print(json.dumps(out), flush=True)
    return 0
