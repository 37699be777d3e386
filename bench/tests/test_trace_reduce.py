"""The reduction from a profiler trace to busy time, per-program device
time and attributed idle gaps: on hand-made events, and on a small trace
recorded on a TPU v5e (``data/tpu_fixture.xplane.pb``, written by
``record_fixture.py``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def ev(plane, line, name, start_us, dur_us):
    return tr.Ev(plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_busy_programs_and_gaps_on_hand_made_events():
    events = [
        ev("/host:CPU", "python", "bench.window", 0, 100),
        ev("/host:CPU", "python", "bench.step", 0, 40),
        ev("/host:CPU", "python", "bench.wait", 40, 30),
        ev("/host:CPU", "python", "bench.step", 70, 30),
        ev("/host:CPU", "python", "bench.submit", 72, 3),
        ev(DEV, tr.MODULES, "jit_decode_fn(7)", 10, 20),
        ev(DEV, tr.OPS, "fusion.1", 10, 5),
        ev(DEV, tr.OPS, "fusion.2", 14, 10),     # overlaps fusion.1
        ev(DEV, tr.MODULES, "jit_decode_fn(7)", 80, 10),
        ev(DEV, tr.OPS, "fusion.1", 80, 10),
        ev(DEV, tr.MODULES, "jit_prefill_fn(3)", 95, 10),   # runs past
        ev(DEV, tr.OPS, "dot.3", 95, 10),
    ]
    s = tr.reduce(events)
    assert s.window_s == pytest.approx(100e-6)
    # union of ops inside the window: [10, 24] + [80, 90] + [95, 100]
    assert s.busy_s == pytest.approx(29e-6)
    assert s.idle_share() == pytest.approx(0.71)
    dec = s.program("jit_decode_fn")
    assert dec.count == 2 and dec.seconds == pytest.approx(30e-6)
    assert s.program("jit_prefill_fn").seconds == pytest.approx(5e-6)
    assert s.program("jit_nothing") is None
    assert s.ops["jit_decode_fn/fusion.1"] == pytest.approx(15e-6)
    # gaps: [0,10] step, [24,80] mid 52 -> wait, [90,95] mid 92.5 -> step
    assert s.idle["bench.step"][0] == pytest.approx(15e-6)
    assert s.idle["bench.wait"][0] == pytest.approx(56e-6)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_decode_fn/fusion.1"
    assert b["idle_gaps"][0][0].startswith("bench.wait")


def test_innermost_span_names_the_gap():
    events = [
        ev("/host:CPU", "python", "bench.window", 0, 100),
        ev("/host:CPU", "python", "bench.step", 0, 100),
        ev("/host:CPU", "python", "bench.submit", 40, 20),
        ev(DEV, tr.OPS, "x", 0, 30),
        ev(DEV, tr.OPS, "x", 70, 30),
    ]
    s = tr.reduce(events)
    assert list(s.idle) == ["bench.submit"]
    assert s.devices == 1


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([ev(DEV, tr.OPS, "x", 0, 1)])


def test_merge():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.program_name("jit_decode_fn(123)") == "jit_decode_fn"
    assert tr.program_name("jit_launch") == "jit_launch"
    assert tr.op_name("%copy.50 = bf16[1,64] copy(bf16[1,64] %x)") == \
        "%copy.50"


def test_recorded_tpu_trace():
    """Recorded on a TPU v5e.  The device's clock there reads about 1.4 ms
    earlier than the host's: the first two of the three executions of
    ``jit_fixture_step`` are stamped before the ``bench.window`` span
    opened, so one lies inside it."""
    events = tr.load_events(str(DATA / "tpu_fixture.xplane.pb"))
    mods = sorted(e.start_ns for e in events if e.line == tr.MODULES)
    steps = sorted(e.start_ns for e in events if e.name == "bench.step")
    assert len(mods) == len(steps) == 3
    assert all(1.0e6 < s - m < 1.6e6 for m, s in zip(mods, steps))
    s = tr.reduce(events)
    step = s.program("jit_fixture_step")
    assert step is not None and step.count == 1
    assert 0 < s.busy_s < s.window_s and s.devices == 1
    assert s.ops["jit_fixture_step/%fusion"] > 0
    # the 30 ms sleep is the longest idle stretch, under bench.wait
    wait = s.idle["bench.wait"]
    assert wait[0] >= 0.03
    assert max(s.idle.values(), key=lambda v: v[0]) is wait
