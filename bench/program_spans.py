"""The program's own span record, read by the per-layer metrics whose
``source`` is ``program_span``.

The program records its spans (``repro.runtime.trace.span``: the serving
engine's steps, rounds and request lifecycles, the executor's dispatch
and read-back, the queue's commands) only while a profiler session is
active, so after a ``--trace 1`` run the record holds the traced stretch
of the window.  A program that keeps no such record reads as empty, and
a metric read from it as no value.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence


def recorded() -> Sequence:
    """The snapshot of the program's span record, oldest first."""
    try:
        from repro.runtime.trace import spans
    except ImportError:
        return ()
    return spans()


def named(record: Sequence, name: str) -> List:
    return sorted((s for s in record if s.name == name),
                  key=lambda s: s.start_ns)


def children(record: Sequence) -> Dict[int, List]:
    """Span id -> the spans recorded under it, by start."""
    out: Dict[int, List] = defaultdict(list)
    for s in sorted(record, key=lambda s: s.start_ns):
        if s.parent is not None:
            out[s.parent].append(s)
    return out


def child(kids: Dict[int, List], span, name: str):
    return next((k for k in kids.get(span.id, ()) if k.name == name), None)


def mean_ms(durations_ns: Sequence[float]) -> Optional[float]:
    return sum(durations_ns) / len(durations_ns) / 1e6 \
        if durations_ns else None
