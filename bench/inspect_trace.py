"""Print what a profiler trace holds: planes, lines, event counts and the
most frequent event names, and the reduction :mod:`bench.trace_reduce`
makes of it.

    python3 bench/inspect_trace.py <dir or .xplane.pb>
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    args = ap.parse_args(argv)
    from bench import trace_reduce as tr
    path = args.path if args.path.endswith(".pb") else \
        tr.find_xplane(args.path)
    events = tr.load_events(path)
    print(f"{path}: {os.path.getsize(path)} bytes, {len(events)} events")
    lines = collections.defaultdict(list)
    for e in events:
        lines[(e.plane, e.line)].append(e)
    for (plane, line), evs in sorted(lines.items()):
        names = collections.Counter(e.name for e in evs)
        busy = sum(e.dur_ns for e in evs) / 1e9
        print(f"{plane!r} / {line!r}: {len(evs)} events, {busy:.6f} s")
        for name, count in names.most_common(12):
            print(f"    {count:7d}  {name[:110]}")
    try:
        s = tr.reduce(events)
    except ValueError as e:
        print(f"no reduction: {e}")
        return 0
    print(f"window {s.window_s:.6f} s, busy {s.busy_s:.6f} s over "
          f"{s.devices} device(s)")
    for name, p in sorted(s.programs.items(), key=lambda kv: -kv[1].seconds):
        print(f"  program {name}: {p.count} calls, {p.seconds:.6f} s")
    for key, rows in s.breakdown().items():
        print(f"  {key}:")
        for name, sec in rows:
            print(f"    {sec:.6f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
