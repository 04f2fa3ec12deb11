"""Run one cell of the benchmark once and build its result line.

The cell's entry in ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the entry
(``bench/entries/<entry>.py``) and gives the limits of its check.
Per-layer metrics are readers of their own,
``bench/metrics/<metric>.py``, each with ``read(view) -> float | None``.
Nothing here names a cell.

A run: find the chips (a TPU, as many as the cell asks, or fail); arm
the compile cache inside the checkout; set up and warm every shape;
measure for ``seconds`` (with ``--trace 1``, profile the window's first
``trace_seconds``); read the memory peak; free the program's state;
replay a seeded sample of what the window produced on the reference;
print the result.  A run is correct when the sample matches the
reference within the mix's limits and the window built no executable.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

from bench import entry, reference
from bench import trace as bench_trace
from bench.common import RunView, Spans, Tracer

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT):
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root / cfg_entry["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, mix


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a run of ``cell`` reports: the end-to-end ones, or
    with a trace the per-layer ones whose end-to-end metric it reports."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if _applies(m, cell) and m["moves"] in names]


def reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (default platform "
                       f"{devs[0].platform!r}); this benchmark does not "
                       f"run on another device")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def arm_compile_cache(root: Path) -> Path:
    """JAX's persistent cache at a fixed path inside the checkout; the
    program takes its cache directory from this variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".bench_cache" / "xla")
    from repro.compat import enable_persistent_compilation_cache
    return enable_persistent_compilation_cache()


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) while armed."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **kw):
        if event == self.event:
            self.count += 1
            self.names.append(str(kw.get("fun_name")))


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_tpu: bool = True,
        shrink: Optional[Callable] = None,
        workers: Optional[int] = None,
        started: Optional[float] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    ``started`` is when the process started (``time.perf_counter()``):
    set-up counts from there, JAX's start-up included.
    ``shrink(cfg, mix)`` (tests only) returns smaller sizes."""
    t = time.perf_counter() if started is None else started
    bench, cell, cfg, mix = find_cell(name, root)
    if shrink is not None:
        cfg, mix = shrink(dict(cfg), dict(mix))
    chips = cell["chips"]
    devices = find_devices(chips, require_tpu)
    dev0 = devices[0]
    peaks = _peaks(dev0, require_tpu)

    cache = arm_compile_cache(root)
    compiles = CompileCounter()
    drv = entry.load(mix["entry"])(cfg, mix, seed, chips)
    try:
        drv.setup()
    except Exception as e:  # the program failed: its answers are wrong
        drv.errors.append(f"set-up: {e!r}")
    setup_s = time.perf_counter() - t
    log(f"[setup] {name}: {setup_s:.3f} s, {compiles.count} executables "
        f"built or loaded, cache {cache}")

    spans = Spans()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = Tracer(trace_dir, mix["trace_seconds"], spans)
    before = compiles.count
    drv.window(seconds, spans, tracer)
    in_window = compiles.count - before
    peak = memory_peak(devices)
    e2e = dict(drv.end_to_end(), setup_s=setup_s)
    counters = dict(drv.counters(tracer), compiles_in_window=in_window)
    log(f"[window] {drv.attempted} {drv.noun} attempted, {drv.failed} "
        f"failed, {drv.wall:.3f} s; executables built or loaded in the "
        f"window: {in_window} {sorted(set(compiles.names[before:]))}")
    log("[window] counters " + json.dumps(counters))
    for err in drv.errors[:5]:
        log(f"[window] error: {err}")
    drv.release()
    gc.collect()

    summary = None
    if trace:
        summary = bench_trace.summarize(
            bench_trace.load(bench_trace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if summary is None and require_tpu:
            raise RuntimeError("the trace holds no TPU plane")

    t = time.perf_counter()
    cases, got = drv.sample()
    want = reference.replay_all(
        cases, True, workers or max(1, (os.cpu_count() or 2) - 1))
    numbers = dict(drv.numbers(got, want) if cases else {},
                   compiles_in_window=in_window)
    limits = dict(mix["limits"], compiles_in_window=0)
    correct = bool(cases) and not drv.errors and all(
        numbers[k] <= limits[k] for k in limits)
    log(f"[check] {len(cases)} {drv.noun} replayed on the reference in "
        f"{time.perf_counter() - t:.1f} s")

    metrics = {}
    view = RunView(cfg=cfg, peaks=peaks, spans=spans, summary=summary,
                   counters=counters)
    for m in cell_metrics(bench, name, trace):
        value = e2e.get(m["name"]) if not trace else reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": drv.attempted,
              "failed": drv.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    for k, v in result["checks"].items():
        log(f"[check] {k} {v['value']} limit {v['limit']}")
    return result


def _peaks(dev, require_tpu: bool) -> dict:
    table = load_json(BENCH / "peaks.json")
    if dev.device_kind in table:
        return table[dev.device_kind]
    if require_tpu:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       f"bench/peaks.json")
    return {}
