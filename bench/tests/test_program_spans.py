"""The per-layer metrics read from the program's span record: each reader
on a hand-made record, on an empty one, and on a program that keeps no
record; and a traced run of the smoke cell that reports all four."""

from __future__ import annotations

import pytest

from bench import harness
from bench import program_spans as ps
from bench.tests import smoke
from bench.tests.smoke import REPO

READERS = ("first_token_ms.chat", "decode_gap_ms.chat",
           "decode_dispatch_ms.chat", "prefill_pad_share.chat")
MS = 1_000_000


def make_record():
    """Two rounds of a step: a prefill beside decode 1, then decode 2,
    on the program's own span type."""
    from repro.runtime.trace import Span

    out, ids = [], iter(range(1, 100))

    def add(name, t0, t1, parent=None, **args):
        s = Span(name, t0 * MS, t1 * MS, next(ids), parent, "w", args)
        out.append(s)
        return s.id

    add("engine.first_token", 0, 70, request=0)
    add("engine.first_token", 100, 160, request=1)
    p = add("executor.prefill", 1, 9, tokens=700, bucket=1024)
    add("executor.dispatch", 1, 2, p)
    add("executor.readback", 2, 9, p)
    p = add("executor.prefill", 101, 106, tokens=1536, bucket=2048)
    add("executor.dispatch", 101, 102, p)
    add("executor.readback", 102, 106, p)
    for t0, disp in ((10, 0.5), (72, 0.25), (135, 0.75)):
        d = add("executor.decode", t0, t0 + 60, rows=30)
        add("executor.dispatch", t0, t0 + disp, d)
        add("executor.readback", t0 + disp, t0 + 58, d)
    add("executor.decode", 200, 210, rows=1)     # no children: skipped
    return tuple(out)


@pytest.fixture
def record(monkeypatch):
    holder = {"spans": ()}
    monkeypatch.setattr(ps, "recorded", lambda: holder["spans"])
    return holder


def read(name):
    return harness.load_metric(REPO, name).read({})


def test_readers_on_a_hand_made_record(record):
    record["spans"] = make_record()
    assert read("first_token_ms.chat") == pytest.approx(65.0)
    # readback ends 68 and 130; next dispatches start 72 and 135
    assert read("decode_gap_ms.chat") == pytest.approx(4.5)
    assert read("decode_dispatch_ms.chat") == pytest.approx(0.5)
    assert read("prefill_pad_share.chat") == pytest.approx(
        100.0 * (1 - 2236 / 3072))


@pytest.mark.parametrize("name", READERS)
def test_readers_on_an_empty_record(record, name):
    assert read(name) is None


def test_a_program_without_a_record_reads_as_empty(monkeypatch):
    import repro.runtime.trace as program_trace
    monkeypatch.delattr(program_trace, "spans")
    assert ps.recorded() == ()
    for name in READERS:
        assert read(name) is None


def test_traced_smoke_run_reports_the_span_metrics(tmp_path):
    root = smoke.make_tree(tmp_path)
    rc, result, out, err = smoke.run_cell(
        root, "smollm-smoke.smoke-chat", seconds=2.0, trace=1)
    assert rc == 0, err[-3000:]
    metrics = result["metrics"]
    for name in READERS:
        assert name in metrics, name
    assert 0 <= metrics["prefill_pad_share.chat"]["value"] < 100
    assert metrics["first_token_ms.chat"]["value"] > 0
    assert metrics["decode_dispatch_ms.chat"]["value"] > 0
