"""The benchmark's harness: finds a cell's files by name, checks the
device, times set-up and the measured window, reads the per-layer
metrics and prints the result line.

Everything that belongs to one configuration, traffic mix or metric
sits in a file of its own, found by the name that ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json   the deployment: pipeline settings,
                                generator, rules, limits of the check
  bench/traffic/<traffic>.json  the mix: which driver runs it
                                (``bench/drivers/<driver>.py``) and its
                                parameters
  bench/metrics/<metric>.py     one reader per per-layer metric:
                                ``read(record) -> float | None``

A driver's ``run(ctx)`` builds the system under test, warms up every
shape its window uses, measures inside ``ctx.window()`` and returns an
``Outcome`` whose ``check()`` compares the window's outputs with the
plain reference once the window has closed.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
KERNELS = ("window_reduce_fwd",)


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, a
    window the generator could not fill)."""


class CompileMonitor:
    """Counts XLA compilations and persistent-cache reads through
    ``jax.monitoring`` (copied from ``chip_smoke.py``)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> "CompileMonitor":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def executables(self) -> int:
        """Executables obtained so far: compiled, or read from the
        persistent cache."""
        return max(self.compiles, self.cache_hits + self.cache_misses)

    def snapshot(self) -> dict:
        return dict(vars(self))


@dataclass
class Outcome:
    """What a driver hands back after its window."""
    end_to_end: Dict[str, float]
    attempted: int
    record: SimpleNamespace            # what the per-layer readers read
    check: Callable[[], "Check"]       # runs after the window has closed
    info: dict = field(default_factory=dict)


@dataclass
class Check:
    readings: Dict[str, float]
    failed: int
    control: Optional[Dict[str, float]] = None


@dataclass
class Ctx:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: bool
    tmp: str
    monitor: CompileMonitor
    t_start: float
    trace_dir: Optional[str] = None
    window_bounds: List[float] = field(default_factory=list)

    @contextmanager
    def window(self):
        """The measured window: host-clock bounds, and under ``--trace 1``
        the profiler, with the window marked for the trace reduction."""
        import jax
        if self.trace:
            self.trace_dir = os.path.join(self.tmp, "trace")
            # host annotations only: the Python tracer records every
            # Python call and slows the host path more than twofold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self.window_bounds.append(time.perf_counter())
                yield
                self.window_bounds.append(time.perf_counter())
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def setup_s(self) -> float:
        return self.window_bounds[0] - self.t_start


def annotate(name: str):
    """A host span in the profiler's trace, so that idle gaps on the
    device can be named after what the harness was driving."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    if spec is None or not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end ones, or with
    ``--trace 1`` the per-layer ones that read it."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


def check_device(chips: int):
    """The devices JAX found; a missing accelerator is an error."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); the benchmark runs "
                         f"only on the chip")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    the fixed ``<checkout>/.jax_cache``."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


def holds(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number compared is finite and within its limit."""
    return all(k in limits and math.isfinite(v) and v <= limits[k]
               for k, v in readings.items())


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, require_chip: bool = True,
             overrides: Optional[dict] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of one cell -> the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    if require_chip:
        devices = check_device(cell["chips"])
    else:
        import jax
        devices = jax.devices()[:cell["chips"]]
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache()
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    for key, val in (overrides or {}).items():
        (config if key in config else traffic)[key] = val
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    metrics = cell_metrics(bench, workload, trace)
    monitor = CompileMonitor().install()
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, control=control, tmp=tmp,
                  monitor=monitor, t_start=t_start)
        out = driver.run(ctx)
        stats = devices[0].memory_stats() or {}
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        trace_sum = None
        if trace:
            from bench.device import trace_reduce
            planes = trace_reduce.load_planes(ctx.trace_dir)
            out.info["trace_lines"] = trace_reduce.describe(planes, 3)
            trace_sum = trace_reduce.summarize(planes, KERNELS)
        out.record.trace = trace_sum
        out.record.device_kind = devices[0].device_kind
        chk = out.check()
    limits = config["limits"]
    correct = holds(chk.readings, limits) and chk.failed == 0
    values = dict(out.end_to_end, setup_s=ctx.setup_s)
    reported = {}
    for m in metrics:
        if trace:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(
                out.record)
        else:
            v = values.get(m["name"])
        if v is not None:
            reported[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if stats.get("bytes_limit"):
        device["memory_limit_bytes"] = int(stats["bytes_limit"])
    result = {"correct": correct, "attempted": out.attempted,
              "failed": chk.failed, "metrics": reported, "device": device}
    if trace_sum is not None:
        device["busy_s"] = trace_sum.busy_s
        device["window_s"] = trace_sum.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_sum.device_ops],
            "idle_gaps": [list(x) for x in trace_sum.idle_gaps]}
    info = dict(out.info, setup_s=ctx.setup_s,
                window_s=ctx.window_bounds[1] - ctx.window_bounds[0],
                compiles=monitor.snapshot())
    if chk.control is not None:
        info["control"] = {k: {"value": v, "limit": limits.get(k)}
                           for k, v in chk.control.items()}
        info["control_correct"] = holds(chk.control, limits)
    result["info"] = info
    result["checks"] = {k: {"value": v, "limit": limits.get(k)}
                        for k, v in chk.readings.items()}
    return result
