"""The benchmark's run: one cell, one seed, one measured window.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to it is found by name:

* ``configs[].file``: the deployment's sizes and its limits; its ``path``
  names the module under ``perfbench/paths/`` that builds and drives it;
* ``perfbench/mixes/<config>.<traffic>.json``: the traffic mix;
* ``perfbench/metrics/<metric>.py`` (or ``<metric up to its first dot>.py``):
  the reader of each per-layer metric.

A run builds the session, warms every shape the cell's traffic uses and
counts all that as set-up. It then measures for ``--seconds``, compares
what the timed path returned with the plain reference, and prints, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device`` and ``checks`` (each compared number beside its
limit, also the last lines of standard error). It needs a TPU and does not
fall back to the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path
from typing import Optional

import numpy as np

from perfbench import compare, tracing, traffic, work

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: JAX's persistent compilation cache, at one fixed place in the checkout.
CACHE_DIR = ROOT / ".perfbench_cache" / "jax"
#: How long requests due in the window may still be served after it.
DRAIN_LIMIT_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoDevice(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


# -- finding a cell's files by name -------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a cell of ``<root>/BENCHMARK.json`` to its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix_path = root / "perfbench" / "mixes" / f"{w['config']}.{w['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def find_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` of a per-layer metric: ``metrics/<name>.py``, or
    ``metrics/<name up to its first dot>.py`` for a quantity read in
    several kinds of cell."""
    for stem in (metric, metric.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"perfbench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def deployment_class(config: dict):
    return importlib.import_module(f"perfbench.paths.{config['path']}"
                                   ).Deployment


# -- device ---------------------------------------------------------------------


def enable_cache() -> None:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size limit, so no eviction bookkeeping that an environment's
    # JAX_COMPILATION_CACHE_MAX_SIZE could switch on
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_tpu(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoDevice(f"JAX sees {len(devices)} {devices[0].platform} "
                       f"device(s); the cell needs {chips} TPU chip(s)")
    return devices[:chips]


class CompileCounter:
    """Programs JAX compiled or read from its persistent cache (``count``),
    and of those the ones it read (``cache_hits``), from its own
    monitoring events."""

    count = 0
    cache_hits = 0
    _registered = False

    def __init__(self):
        if not CompileCounter._registered:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
            CompileCounter._registered = True

    @staticmethod
    def _on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            CompileCounter.count += 1

    @staticmethod
    def _on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            CompileCounter.cache_hits += 1


# -- the window -------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """Per request: when it was due, when the call that served it started
    and when its decision came back (NaN: never)."""

    t0: float
    seconds: float
    due: np.ndarray
    start: np.ndarray
    done: np.ndarray
    batches: list

    @property
    def n_due(self) -> int:
        return len(self.due)

    @property
    def served(self) -> np.ndarray:
        return np.isfinite(self.done)

    @property
    def close(self) -> float:
        """When the last decision of the window came back."""
        return float(np.nanmax(self.done)) if self.served.any() else self.t0


def _wait_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 2e-4:
        time.sleep(delay - 1e-4)
    while time.perf_counter() < t:
        pass


def run_open(dep, offsets: np.ndarray, seconds: float,
             spans: tracing.Spans) -> Window:
    """Requests arrive at ``t0 + offsets``; each call takes every request
    that is due, up to the deployment's largest batch. Requests due in the
    window are served until ``DRAIN_LIMIT_S`` past its end."""
    n = len(offsets)
    start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    batches = []
    with spans.span(tracing.WINDOW):
        t0 = time.perf_counter()
        due = t0 + offsets
        give_up = t0 + seconds + DRAIN_LIMIT_S
        i = 0
        while i < n:
            now = time.perf_counter()
            if now > give_up:
                break
            if due[i] > now:
                _wait_until(min(due[i], give_up))
                continue
            j = min(int(np.searchsorted(due, now, side="right")),
                    i + dep.max_batch)
            dep.serve(i, j)
            t1 = time.perf_counter()
            start[i:j] = now
            done[i:j] = t1
            batches.append(j - i)
            i = j
    return Window(t0, seconds, due, start, done, batches)


def run_closed(dep, batch: int, seconds: float,
               spans: tracing.Spans) -> Window:
    """One client: the next call of ``batch`` requests starts when the
    last returns, until the window ends."""
    starts, dones = [], []
    with spans.span(tracing.WINDOW):
        t0 = time.perf_counter()
        t_end = t0 + seconds
        i = 0
        now = t0
        while now < t_end:
            dep.serve(i, i + batch)
            t1 = time.perf_counter()
            starts.append(now)
            dones.append(t1)
            i += batch
            now = t1
    start = np.repeat(starts, batch)
    done = np.repeat(dones, batch)
    return Window(t0, seconds, start.copy(), start, done,
                  [batch] * len(starts))


def run_window(dep, mix: dict, seed: int, seconds: float,
               spans: tracing.Spans) -> Window:
    if mix["loop"] == "open":
        return run_open(dep, traffic.arrivals(mix, seed, seconds), seconds,
                        spans)
    return run_closed(dep, int(mix["batch"]), seconds, spans)


def latencies_s(win: Window) -> np.ndarray:
    """Due-to-decision seconds of every request due in the window; one
    never served counts as waiting until the drain gave up."""
    give_up = win.t0 + win.seconds + DRAIN_LIMIT_S
    return np.where(win.served, win.done, give_up) - win.due


def end_to_end(win: Window, setup_s: float) -> dict:
    lat = latencies_s(win)
    return {
        "decisions_per_s": int(win.served.sum()) / (win.close - win.t0),
        "decision_p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "decision_p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "setup_s": setup_s,
    }


# -- one run ------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = ROOT, need_tpu: bool = True,
        cell: Optional[Cell] = None) -> dict:
    """One run of a cell; returns the result object. ``need_tpu=False``
    and ``cell`` (a cell cut to a CPU's size) serve the harness's tests."""
    cell = cell or load_cell(workload, root)
    import jax
    devices = require_tpu(cell.chips) if need_tpu else jax.devices()[:1]
    if need_tpu:
        enable_cache()
    counter = CompileCounter()
    spans = tracing.Spans(annotate=trace)
    dep = deployment_class(cell.config)(cell.config, cell.mix, seed, spans)
    dep.setup()
    compiles_setup, hits_setup = CompileCounter.count, CompileCounter.cache_hits
    # every window starts from the same collector state; the collector
    # then runs in the window as it would in the program's own process
    gc.collect()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    win = run_window(dep, cell.mix, seed, seconds, spans)
    compiles_window = CompileCounter.count - compiles_setup
    device_trace = None
    if trace:
        jax.profiler.stop_trace()
        device_trace = tracing.load(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    lat = latencies_s(win)
    print(json.dumps({
        "cell": cell.name, "seed": seed, "compiles_in_setup": compiles_setup,
        "cache_hits_in_setup": hits_setup,
        "compiles_in_window": compiles_window, "setup_s": setup_s,
        "requests": win.n_due, "calls": len(win.batches),
        "mean_batch": float(np.mean(win.batches)) if win.batches else 0.0,
        "max_batch": max(win.batches, default=0),
        "queue_wait_p95_ms": float(np.nanpercentile(
            win.start - win.due, 95)) * 1e3 if win.n_due else 0.0,
        "latency_max_ms": float(lat.max()) * 1e3 if win.n_due else 0.0,
    }), flush=True)

    ctx = types.SimpleNamespace(
        dep=dep, config=cell.config, mix=cell.mix, window=win, spans=spans,
        trace=device_trace, seconds=seconds,
        peaks=work.load_peaks(devices[0].device_kind) if need_tpu else None)
    per_layer = {}
    if trace:
        for m in cell.per_layer:
            value = find_reader(m["name"])(ctx)
            if value is not None:
                per_layer[m["name"]] = {"value": float(value),
                                        "unit": m["unit"]}
    dep.release()
    numbers = dep.check(win)
    ok, table = compare.verdict(numbers, cell.config["limits"])
    failed = int(numbers["missing"])
    if compiles_window:
        ok = False

    if trace:
        metrics = per_layer
    else:
        e2e = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok), "attempted": win.n_due, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tracing.busy_seconds(device_trace)
        device["window_s"] = device_trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in tracing.top_ops(device_trace)],
            "idle_gaps": [list(x) for x in tracing.idle_gaps(device_trace)[:10]],
        }
    table["compiles_in_window"] = {"value": compiles_window, "limit": 0}
    result["checks"] = table
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    except NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
