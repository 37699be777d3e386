"""Shared plumbing of the chip benchmark.

Everything here is independent of any one cell: finding a cell's files by
the names in ``BENCHMARK.json``, loading drivers and per-layer metric
readers by file name, the device check, the compile cache, the profiler
window, percentiles, and the result line.  A cell, a configuration or a
per-layer metric is added with new files under ``bench/`` and a new entry
in ``BENCHMARK.json``; nothing in this module names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

class SpecError(RuntimeError):
    """A cell, configuration, driver or metric named in BENCHMARK.json has
    no file, or its file disagrees with the entry."""


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""
    name: str
    entry: Dict[str, Any]          # the BENCHMARK.json workload entry
    workload: Dict[str, Any]       # bench/workloads/<name>.json
    config: Dict[str, Any]         # the configuration file, parsed
    config_entry: Dict[str, Any]   # the BENCHMARK.json configs entry
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path                     # the checkout holding BENCHMARK.json


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise SpecError(f"no BENCHMARK.json in {root}")
    spec = json.loads(spec_path.read_text())
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_entry = configs[entry["config"]]
    wl_path = root / "bench" / "workloads" / f"{name}.json"
    if not wl_path.is_file():
        raise SpecError(f"workload {name!r} has no file {wl_path}")
    workload = json.loads(wl_path.read_text())
    for key in ("config", "traffic"):
        if workload.get(key) != entry[key]:
            raise SpecError(f"{wl_path.name}: {key} {workload.get(key)!r} "
                            f"!= BENCHMARK.json's {entry[key]!r}")
    config = json.loads((root / config_entry["file"]).read_text())
    return Cell(name=name, entry=entry, workload=workload, config=config,
                config_entry=config_entry,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)],
                root=root)


def load_module(path: Path, label: str):
    """Import one file of ``bench/`` by path (names may hold dots)."""
    if not path.is_file():
        raise SpecError(f"{label}: no file {path}")
    mod_name = "bench_" + "".join(c if c.isalnum() else "_"
                                  for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(cell: Cell):
    return load_module(cell.root / "bench" / "drivers"
                       / f"{cell.workload['driver']}.py",
                       f"driver of {cell.name}")


def load_metric(root: Path, name: str):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"per-layer metric {name}")


# ---------------------------------------------------------------------------
# device, compile cache, compile counting
# ---------------------------------------------------------------------------

def require_chips(chips: int, allow_cpu: bool = False) -> List[Any]:
    """The JAX devices a cell runs on; raises :class:`NoChip` unless JAX
    finds at least ``chips`` accelerators (``allow_cpu`` is for the
    benchmark's own CPU tests, never for a measurement)."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devs) < chips and not allow_cpu:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips] if len(devs) >= chips else devs


def device_record(devices) -> Dict[str, Any]:
    """``device`` of the result line; the memory peak is the fullest
    chip's, read by the caller through :func:`memory_peak_bytes`."""
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program
    however fast it compiled, so only a checkout's first run compiles."""
    import jax
    from repro.backend import enable_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts JAX traces and backend compiles while :attr:`armed`."""

    KEYS = ("/jax/core/compile/backend_compile_duration",
            "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.counts = {k: 0 for k in self.KEYS}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if self.armed and name in self.counts:
            with self._lock:
                self.counts[name] += 1

    @property
    def compiles(self) -> int:
        return self.counts[self.KEYS[0]]

    @property
    def traces(self) -> int:
        return self.counts[self.KEYS[1]]


class GcWatch:
    """The interpreter's garbage collections while :attr:`armed`: how many
    of each generation, and the longest pause of each (seconds)."""

    def __init__(self):
        import gc
        self.armed = False
        self.count = [0, 0, 0]
        self.longest = [0.0, 0.0, 0.0]
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.armed and self._t0 is not None:
            g = info["generation"]
            self.count[g] += 1
            self.longest[g] = max(self.longest[g],
                                  time.perf_counter() - self._t0)

    def close(self) -> None:
        import gc
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------

def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """Traces one stretch of the measured window into a temporary
    directory under ``$TMPDIR``; :meth:`summary` reduces it with
    :mod:`bench.trace_reduce` and deletes the files."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.active = False
        self.done = False
        self.t0 = self.t1 = None           # host perf_counter bounds
        self._window = None

    def start(self) -> None:
        if not self.enabled or self.active or self.done:
            return
        import jax
        from bench.trace_reduce import WINDOW
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def summary(self):
        if self.dir is None:
            return None
        from bench import trace_reduce
        try:
            path = trace_reduce.find_xplane(self.dir)
            return trace_reduce.reduce(trace_reduce.load_events(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# numbers
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The ``q``-th percentile over *all* samples (linear interpolation
    between closest ranks, numpy's default); raises on no samples."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Check:
    """One number compared against its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to :mod:`bench.run`."""
    end_to_end: Dict[str, float]
    setup_s: float
    attempted: int
    failed: int
    checks: List[Check]
    devices: List[Any]
    memory_peak_bytes: Optional[int]
    trace: Any = None                  # trace_reduce.Summary, traced runs
    obs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    errors: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and not self.errors and \
            self.failed == 0 and all(c.ok for c in self.checks)


def result_line(outcome: Outcome, metrics: Dict[str, Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]]) -> str:
    device = device_record(outcome.devices)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    if outcome.trace is not None:
        device["busy_s"] = outcome.trace.busy_s
        device["window_s"] = outcome.trace.window_s
    doc: Dict[str, Any] = {
        "correct": outcome.correct, "attempted": outcome.attempted,
        "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in outcome.checks}
    return json.dumps(doc)


def print_checks(outcome: Outcome) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for e in outcome.errors:
        print(f"error: {e}", file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()


__all__ = ["Cell", "Check", "CompileCounter", "GcWatch", "NoChip", "Outcome",
           "Profiler", "SpecError", "device_record", "enable_compile_cache",
           "load_cell", "load_driver", "load_metric", "memory_peak_bytes",
           "percentile", "print_checks", "require_chips", "result_line",
           "span"]
