"""Program spans (``repro.runtime.trace.span``) and the names the
profiler shows for the program's device code (docs/runtime.md
§Observability).

* tracing off: a span is one check, records nothing and builds no
  ``jax.profiler.TraceAnnotation``;
* inside a JAX profiler session the serving engine and the queue record
  their spans with parents and request ids, and the profile's host
  plane holds a twin of each with the same counts as stats;
* the jitted executor records ``executor.dispatch`` / ``readback``
  under ``executor.decode`` and ``executor.prefill``;
* ``ChromeTrace.add_spans`` puts a ``Context.trace()`` window's spans
  beside the DAG slices;
* the model's named scopes reach the decode step's op metadata, and a
  DSL launch's device program carries its kernel's name.
"""

import collections
import glob
import os

import jax
import numpy as np

from repro.runtime import trace as T
from repro.runtime import validate_trace
from repro.runtime.context import Context
from repro.serving import Request, ServingEngine, StubExecutor

ENGINE_SPANS = {"engine.step", "engine.round", "engine.queued",
                "engine.first_token"}


def stub_engine(context=None):
    return ServingEngine(None, None, None, batch_slots=2, max_seq=32,
                         executor=StubExecutor(batch_slots=2, max_seq=32),
                         context=context or Context())


def serve(eng, n=3):
    reqs = [Request(prompt=np.arange(2 + i, dtype=np.int32),
                    max_new_tokens=3) for i in range(n)]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    assert all(r.done for r in reqs)
    return reqs


def by_name(recorded):
    out = collections.defaultdict(list)
    for s in recorded:
        out[s.name].append(s)
    return out


def test_tracing_off_records_nothing_and_builds_no_annotation(monkeypatch):
    built, checks = [], []

    def no_annotation(*args, **kwargs):
        built.append(args)
        raise AssertionError("annotation built while tracing is off")

    def profiling():
        checks.append(1)
        return False

    monkeypatch.setattr(T, "TraceAnnotation", no_annotation)
    monkeypatch.setattr(T, "_profiling", profiling)
    T.clear_spans()
    s = T.span("x", rows=3)
    assert s.id is None and len(checks) == 1
    with s as inner:
        inner.set(tokens=1)
    eng = stub_engine()
    serve(eng)
    assert T.spans() == () and not built
    assert not eng._open_spans


def test_profiler_session_records_engine_and_queue_spans(tmp_path):
    from jax.profiler import ProfileData

    T.clear_spans()
    eng = stub_engine()
    with jax.profiler.trace(str(tmp_path)):
        reqs = serve(eng)
    assert T.span("x").id is None         # off again once it stops
    recorded = T.spans()
    names = by_name(recorded)
    assert ENGINE_SPANS | {"queue.command", "queue.finish"} <= set(names)
    ids = {s.id: s for s in recorded}

    # parents: a round runs under its step; a command runs under the
    # round that enqueued it, on a worker thread
    for r in names["engine.round"]:
        assert ids[r.parent].name == "engine.step"
    for c in names["queue.command"]:
        assert ids[c.parent].name == "engine.round"
        assert c.thread != ids[c.parent].thread
        assert c.args["wait_us"] >= 0
    for f in names["queue.finish"]:
        assert ids[f.parent].name == "engine.round"
    # each request was queued once and got its first token once
    rids = sorted(r.id for r in reqs)
    for name in ("engine.queued", "engine.first_token"):
        assert sorted(s.args["request"] for s in names[name]) == rids
    for s in recorded:
        assert s.end_ns >= s.start_ns
    assert sum(r.args["prefills"] for r in names["engine.round"]) == 3

    # the profile's host plane holds a twin of every span, same stats
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    twins = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    stats = {k: v for k, v in e.stats
                             if not isinstance(v, float)}
                    twins[(e.name, tuple(sorted(stats.items())))] += 1
    want = collections.Counter(
        (s.name, tuple(sorted((k, v) for k, v in s.args.items()
                              if not isinstance(v, float))))
        for s in recorded)
    assert twins == want


def test_jax_executor_spans_dispatch_and_readback():
    from repro import configs
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import init_params

    cfg = configs.get_smoke("smollm-135m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, BASELINE_RULES, batch_slots=2,
                        max_seq=32, context=Context())
    rng = np.random.default_rng(1)

    def reqs():
        return [Request(prompt=rng.integers(0, cfg.vocab, n)
                        .astype(np.int32), max_new_tokens=3)
                for n in (5, 9)]

    eng.generate(reqs())                    # compile outside the record
    T.clear_spans()
    with T.record_spans():
        eng.generate(reqs())
    names = by_name(T.spans())
    kids = collections.defaultdict(list)
    for s in T.spans():
        kids[s.parent].append(s.name)

    decodes = names["executor.decode"]
    assert decodes and {d.args["rows"] for d in decodes} <= {1, 2}
    assert 2 in {d.args["rows"] for d in decodes}
    for d in decodes + names["executor.prefill"]:
        assert sorted(kids[d.id]) == ["executor.dispatch",
                                      "executor.readback"]
    assert sorted((p.args["tokens"], p.args["bucket"])
                  for p in names["executor.prefill"]) == [(5, 8), (9, 16)]
    assert len(names["executor.insert"]) == 2
    ids = {s.id: s for s in T.spans()}
    for d in decodes:
        cmd = ids[d.parent]
        assert cmd.name == "queue.command"
        assert cmd.args["command"].startswith("decode:")
        assert ids[cmd.parent].name == "engine.round"


def test_chrome_trace_export_includes_engine_spans(tmp_path):
    ctx = Context()
    eng = stub_engine(ctx)
    T.clear_spans()
    with ctx.trace() as tr:
        serve(eng)
    assert tr.add_spans(T.spans()) > 0
    events = tr.trace_events()
    validate_trace(events)
    spans = [e for e in events if e.get("cat") == "span"]
    assert ENGINE_SPANS | {"queue.finish"} <= {e["name"] for e in spans}
    assert "queue.command" not in {e["name"] for e in spans}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in spans)
    # the DAG slices are still there, one per command
    assert any(e.get("cat") == "native" for e in events)
    doc = tr.export(str(tmp_path / "serve.json"))
    validate_trace(doc["traceEvents"])


def test_decode_step_carries_model_scopes():
    import re

    from repro import configs
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import abstract_params, init_caches
    from repro.serving.executor import step_functions

    cfg = configs.get_smoke("smollm-135m")
    _, _, decode = step_functions(cfg, BASELINE_RULES, {})
    caches = init_caches(cfg, 2, 32, abstract=True)
    toks = jax.ShapeDtypeStruct((2, 1), np.int32)
    occ = jax.ShapeDtypeStruct((2,), np.bool_)
    hlo = decode.lower(abstract_params(cfg), toks, caches, occ) \
        .compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("attention", "attention/cache_update", "mlp", "norm",
                  "embed", "lm_head"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    assert any("/while/body/" in n and "/mlp/" in n for n in op_names)


def test_dsl_device_program_carries_kernel_name():
    from repro.core import KernelBuilder
    from repro.core.api import _compile_kernel

    def build():
        b = KernelBuilder("scale")
        x = b.arg_buffer("x", "float32")
        y = b.arg_buffer("y", "float32")
        g = b.global_id(0)
        y[g] = x[g] * 2.0
        return b.finish()

    ck = _compile_kernel(build, (64,), cache=False)
    bufs = {"x": np.arange(256, dtype=np.float32),
            "y": np.zeros(256, np.float32)}
    out = ck(bufs, (256,))
    np.testing.assert_array_equal(np.asarray(out["y"]), bufs["x"] * 2.0)
    (fn,) = ck._jit_cache.values()
    assert fn.lower(bufs, {}).as_text().startswith(
        "module @jit_dsl_scale")


def test_record_is_bounded_and_clears():
    T.clear_spans()
    with T.record_spans():
        for _ in range(T.SPAN_CAPACITY + 5):
            with T.span("engine.step"):
                pass
    recorded = T.spans()
    assert len(recorded) == T.SPAN_CAPACITY
    assert recorded[0].id < recorded[-1].id
    T.clear_spans()
    assert T.spans() == ()
