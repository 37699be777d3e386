"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it reads (planes and lines as the JAX profiler writes them for a
TPU; see PERF.md, "Layers"):

* device planes, ``/device:TPU:<n>``: the ``XLA Modules`` line (one event
  per execution of a compiled program, named after the jitted function,
  e.g. ``jit_decode_fn(42)``) and the ``XLA Ops`` line (one event per
  HLO operation run);
* host planes (``/host:CPU``): the benchmark's own spans, named
  ``bench.*`` (``jax.profiler.TraceAnnotation``); ``bench.window``
  bounds the traced stretch of the measured window.

From those it computes, inside the window: the busy time of each device
(the union of its op intervals, or of its module intervals where a
device has no op line), the device time and call count of each program,
the time of each operation, and the idle gaps between busy intervals,
each attributed to the innermost ``bench.*`` span that covers its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
MODULES, OPS = "XLA Modules", "XLA Ops"
NO_SPAN = "outside any bench span"


@dataclasses.dataclass
class Ev:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {found}")
    return found[0]


def load_events(path: str) -> List[Ev]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def program_name(event_name: str) -> str:
    """``jit_decode_fn(42)`` -> ``jit_decode_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep
    the instruction's name: ``%copy.50 = bf16[...] copy(...)`` ->
    ``%copy.50``."""
    return event_name.split(" = ", 1)[0]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclasses.dataclass
class Program:
    seconds: float = 0.0
    count: int = 0


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices used
    devices: int
    programs: Dict[str, Program]
    ops: Dict[str, float]               # "program/op" -> seconds
    idle: Dict[str, List[float]]        # host span -> [seconds, gaps]

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, name: str) -> Optional[Program]:
        p = self.programs.get(name)
        return p if p is not None and p.count else None

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[f"{n} ({int(c)} gaps)", s]
                              for n, (s, c) in idle]}


def _clip(ev: Ev, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
    return (s, e) if e > s else None


def reduce(events: List[Ev]) -> Summary:
    windows = [e for e in events
               if e.name == WINDOW and not is_device_plane(e.plane)]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = windows[0].start_ns, windows[0].end_ns

    by_plane: Dict[str, Dict[str, List[Ev]]] = {}
    for e in events:
        if is_device_plane(e.plane) and e.line in (MODULES, OPS):
            by_plane.setdefault(e.plane, {}).setdefault(e.line, []) \
                .append(e)

    programs: Dict[str, Program] = {}
    ops: Dict[str, float] = {}
    busy_per_device, busy_union = [], []
    for lines in by_plane.values():
        mods = sorted(lines.get(MODULES, []), key=lambda e: e.start_ns)
        for m in mods:
            c = _clip(m, w0, w1)
            if c is None:
                continue
            p = programs.setdefault(program_name(m.name), Program())
            p.seconds += (c[1] - c[0]) / 1e9
            p.count += 1
        starts = [m.start_ns for m in mods]
        busy_src = lines.get(OPS) or mods
        spans = []
        for o in busy_src:
            c = _clip(o, w0, w1)
            if c is None:
                continue
            spans.append(c)
            if o.line == OPS:
                i = bisect.bisect_right(starts, o.start_ns) - 1
                owner = program_name(mods[i].name) \
                    if i >= 0 and mods[i].end_ns >= o.start_ns else "?"
                key = f"{owner}/{op_name(o.name)}"
                ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) / 1e9
        merged = merge(spans)
        if merged:
            busy_per_device.append(sum(e - s for s, e in merged) / 1e9)
            busy_union.extend(merged)

    host_spans = sorted(
        (e for e in events if not is_device_plane(e.plane)
         and e.name.startswith(SPAN_PREFIX) and e.name != WINDOW),
        key=lambda e: e.start_ns)
    span_starts = [h.start_ns for h in host_spans]
    idle: Dict[str, List[float]] = {}
    busy = merge(busy_union)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        # spans nest, so the covering span that started last is the
        # innermost one
        label = NO_SPAN
        i = bisect.bisect_right(span_starts, mid) - 1
        for h in host_spans[max(0, i - 256):i + 1][::-1]:
            if h.end_ns >= mid:
                label = h.name
                break
        slot = idle.setdefault(label, [0.0, 0])
        slot[0] += (e - s) / 1e9
        slot[1] += 1

    n_dev = len(busy_per_device)
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy_per_device) / n_dev if n_dev else 0.0,
                   devices=n_dev, programs=programs, ops=ops, idle=idle)


__all__ = ["Ev", "Program", "Summary", "find_xplane", "is_device_plane",
           "load_events", "merge", "op_name", "program_name", "reduce"]
