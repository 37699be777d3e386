"""Deterministic continuous-batching scheduler tests (docs/serving.md).

Covers the scheduler invariants the tentpole promises, each as a small
deterministic scenario:

* mid-decode eviction refills the slot **on the same step**;
* token streams bitwise-identical to serial one-request-at-a-time
  execution (real jitted model, mixed prompt lengths, co-tenant slots);
* OOM preemption requeues without losing a request, surfacing the typed
  :class:`~repro.runtime.bufalloc.OutOfMemory`;
* ``kv_stats`` shows pages returned per *eviction* (not per group);
* short tails are masked empty slots, never duplicated requests (the
  old ``_make_groups`` padding bug);
* an injected device-side DAG failure surfaces the typed error on the
  affected request while siblings complete (ROADMAP item 5 seed).

The scheduler-only scenarios run on the deterministic
:class:`~repro.serving.executor.StubExecutor` — same engine, same DAG,
same BufferPool paging, no tracing — with
``StubExecutor.expected_tokens`` as the closed-form oracle.
"""

import numpy as np
import pytest

from repro.core.errors import (DeviceLostError, InvalidArgError,
                               ReproError)
from repro.runtime.bufalloc import OutOfMemory
from repro.serving import Request, RequestState, ServingEngine, StubExecutor


def stub_engine(slots=2, max_seq=64, **kw):
    ex = StubExecutor(batch_slots=slots, max_seq=max_seq)
    return ServingEngine(None, None, None, batch_slots=slots,
                         max_seq=max_seq, executor=ex, **kw), ex


def req(rng, plen=None, max_new=4, **kw):
    plen = plen or int(rng.integers(3, 9))
    return Request(prompt=rng.integers(0, 500, plen).astype(np.int32),
                   max_new_tokens=max_new, **kw)


def expect(r):
    return StubExecutor.expected_tokens(r.prompt, r.max_new_tokens,
                                        eos_token=r.eos_token)


# --------------------------------------------------------------------------
# same-step refill
# --------------------------------------------------------------------------

def test_eviction_refills_slot_on_same_step():
    eng, ex = stub_engine(slots=1)
    a = Request(prompt=np.arange(3, dtype=np.int32), max_new_tokens=2)
    b = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=3)
    eng.submit(a)
    eng.submit(b)
    eng.step()                      # prefill a -> token 0
    out = eng.step()                # decode finishes a; b refills NOW
    assert a in out and a.done
    # b was admitted and prefilled within the same step() call
    assert b.state == RequestState.RUNNING
    assert len(b.out_tokens) == 1
    eng.drain()
    assert b.out_tokens == expect(b)


def test_long_request_no_longer_stalls_neighbours():
    """One long generation plus many short ones: with continuous
    batching the shorts flow through the freed slot while the long one
    keeps decoding; the fixed baseline barriers on the long request."""
    def serve(scheduler):
        eng, ex = stub_engine(slots=2, scheduler=scheduler)
        rng = np.random.default_rng(0)
        long = req(rng, plen=5, max_new=24)
        shorts = [req(rng, max_new=2) for _ in range(5)]
        for r in [long] + shorts:
            eng.submit(r)
        eng.drain()
        assert long.out_tokens == expect(long)
        for r in shorts:
            assert r.out_tokens == expect(r)
        return ex.decode_calls

    continuous, fixed = serve("continuous"), serve("fixed")
    # fixed-slot pays a full barriered round per short-request group
    assert continuous < fixed


# --------------------------------------------------------------------------
# bitwise-identical to serial execution (real model)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_continuous_tokens_bitwise_identical_to_serial():
    import jax

    from repro import configs
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import init_params

    cfg = configs.get_smoke("smollm-135m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in (4, 6, 5, 7)]
    budgets = [3, 5, 2, 4]

    # serial oracle: one request at a time, batch width 1
    serial = ServingEngine(cfg, params, BASELINE_RULES, batch_slots=1,
                           max_seq=32)
    serial_out = []
    for p, m in zip(prompts, budgets):
        r = Request(prompt=p.copy(), max_new_tokens=m)
        serial.generate([r])
        serial_out.append(r.out_tokens)

    # continuous engine: all requests co-resident across 2 slots, with
    # staggered arrivals so slot assignments interleave
    eng = ServingEngine(cfg, params, BASELINE_RULES, batch_slots=2,
                        max_seq=32)
    reqs = [Request(prompt=p.copy(), max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    pending = list(reqs)
    while pending or eng.scheduler_stats["waiting"] or \
            eng.scheduler_stats["running"]:
        if pending:
            eng.submit(pending.pop(0))
        eng.step()
    for r, ref in zip(reqs, serial_out):
        assert r.done and r.out_tokens == ref, \
            "continuous batching changed a request's token stream"


# --------------------------------------------------------------------------
# OOM preemption
# --------------------------------------------------------------------------

def test_oom_preemption_requeues_without_loss():
    ex = StubExecutor(batch_slots=2, max_seq=64, bytes_per_token=64)
    # page = 4 tokens * 64 B; budget of 12 pages cannot hold two
    # requests growing to ~38 tokens each
    eng = ServingEngine(None, None, None, batch_slots=2, max_seq=64,
                        executor=ex, page_tokens=4,
                        kv_budget_bytes=12 * 4 * 64)
    rng = np.random.default_rng(1)
    r1, r2 = req(rng, plen=8, max_new=30), req(rng, plen=9, max_new=30)
    eng.submit(r1)
    eng.submit(r2)
    done = eng.drain()
    assert {id(r) for r in done} == {id(r1), id(r2)}
    # zero dropped: both completed despite preemption, typed error kept
    assert r1.done and r2.done
    assert eng.scheduler_stats["preemptions"] >= 1
    assert isinstance(eng.last_oom, OutOfMemory)
    assert isinstance(eng.last_oom, ReproError)
    assert eng.last_oom.code == -4
    # recompute-style preemption regenerated identical streams
    assert r1.out_tokens == expect(r1)
    assert r2.out_tokens == expect(r2)
    # the preempted request observed at least one restart
    assert r1.preemptions + r2.preemptions == \
        eng.scheduler_stats["preemptions"]
    assert eng.kv_stats["pages_live"] == 0


def test_preemption_victim_is_lowest_priority_latest_arrival():
    ex = StubExecutor(batch_slots=2, max_seq=64, bytes_per_token=64)
    eng = ServingEngine(None, None, None, batch_slots=2, max_seq=64,
                        executor=ex, page_tokens=4,
                        kv_budget_bytes=10 * 4 * 64)
    rng = np.random.default_rng(2)
    hi = req(rng, plen=6, max_new=28, priority=1)
    lo = req(rng, plen=6, max_new=28, priority=0)
    eng.submit(hi)
    eng.submit(lo)
    eng.drain()
    assert hi.done and lo.done
    assert lo.preemptions >= 1, "low priority should be the victim"
    assert hi.preemptions == 0
    assert hi.out_tokens == expect(hi) and lo.out_tokens == expect(lo)


def test_sole_resident_oom_fails_typed():
    """A request that cannot fit even alone fails with the typed
    OutOfMemory instead of livelocking the scheduler."""
    ex = StubExecutor(batch_slots=1, max_seq=64, bytes_per_token=64)
    eng = ServingEngine(None, None, None, batch_slots=1, max_seq=64,
                        executor=ex, page_tokens=4,
                        kv_budget_bytes=3 * 4 * 64)   # 12 tokens max
    r = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=30)
    eng.submit(r)
    eng.drain()
    assert not r.done and r.state == RequestState.FAILED
    assert isinstance(r.error, OutOfMemory)
    assert eng.kv_stats["pages_live"] == 0


# --------------------------------------------------------------------------
# paged KV accounting
# --------------------------------------------------------------------------

def test_kv_stats_pages_returned_per_eviction():
    eng, ex = stub_engine(slots=2, page_tokens=4)
    rng = np.random.default_rng(3)
    reqs = [req(rng, plen=6, max_new=3) for _ in range(4)]
    frees_after = []
    evicted = 0
    for r in reqs:
        eng.submit(r)
    while any(not (r.done or r.error) for r in reqs):
        done = eng.step()
        if done:
            evicted += len(done)
            frees_after.append(eng.kv_stats["frees"])
    # frees grow with every eviction step (pages return per request,
    # not one block per group at the end)
    assert evicted == 4
    assert all(b > a for a, b in zip(frees_after, frees_after[1:])), \
        frees_after
    st = eng.kv_stats
    # every allocated page came back, page by page
    assert st["pages_live"] == 0 and st["kv_used_bytes"] == 0
    sched = eng.scheduler_stats
    assert sched["pages_freed"] == sched["pages_allocated"]
    # each request needed ceil((plen + new) / page_tokens) >= 2 pages
    assert sched["pages_allocated"] >= 2 * len(reqs)


def test_kv_pages_sized_from_executor_footprint():
    ex = StubExecutor(batch_slots=2, max_seq=64, bytes_per_token=128)
    eng = ServingEngine(None, None, None, batch_slots=2, max_seq=64,
                        executor=ex, page_tokens=8)
    st = eng.kv_stats
    assert st["bytes_per_token"] == 128
    assert st["page_bytes"] == 128 * 8
    assert st["kv_bytes_per_group"] == ex.cache_bytes(2, 64)


# --------------------------------------------------------------------------
# short tails: masked empty slots, no duplicate compute
# --------------------------------------------------------------------------

def test_tail_requests_not_duplicated():
    """Regression for the _make_groups padding bug: 3 requests on 2
    slots used to pad the tail group with a duplicated request."""
    eng, ex = stub_engine(slots=2)
    rng = np.random.default_rng(4)
    reqs = [req(rng, max_new=3) for _ in range(3)]
    done = eng.generate(reqs)
    assert len(done) == 3
    # exactly one prefill per submitted request — no duplicate compute
    assert ex.prefill_calls == 3
    for r in reqs:
        assert r.out_tokens == expect(r)


def test_single_request_on_wide_engine():
    eng, ex = stub_engine(slots=4)
    r = Request(prompt=np.arange(5, dtype=np.int32), max_new_tokens=4)
    eng.submit(r)
    eng.drain()
    assert r.done and ex.prefill_calls == 1
    assert r.out_tokens == expect(r)


# --------------------------------------------------------------------------
# fault injection (ROADMAP item 5 seed)
# --------------------------------------------------------------------------

def test_decode_fault_fails_one_request_siblings_complete():
    eng, ex = stub_engine(slots=2)
    rng = np.random.default_rng(5)
    good, bad, late = req(rng, max_new=6), req(rng, max_new=6), \
        req(rng, max_new=2)
    eng.submit(good)
    eng.submit(bad)
    eng.submit(late)
    eng.inject_fault(bad, stage="decode")
    eng.drain()
    # the injected device-side failure surfaced as the typed error on
    # exactly the affected request's result
    assert not bad.done and bad.state == RequestState.FAILED
    assert isinstance(bad.error, DeviceLostError)
    assert isinstance(bad.error, ReproError) and bad.error.code == -2
    # siblings (co-resident and queued-behind) completed, bit-exact
    assert good.done and good.out_tokens == expect(good)
    assert late.done and late.out_tokens == expect(late)
    # the failed request's pages came back
    assert eng.kv_stats["pages_live"] == 0


def test_prefill_fault_fails_one_request_siblings_complete():
    eng, ex = stub_engine(slots=2)
    rng = np.random.default_rng(6)
    good, bad = req(rng, max_new=4), req(rng, max_new=4)
    eng.submit(good)
    eng.submit(bad)
    eng.inject_fault(bad, stage="prefill",
                     error=DeviceLostError("boom"))
    eng.drain()
    assert isinstance(bad.error, DeviceLostError)
    assert str(bad.error) == "boom"
    assert good.done and good.out_tokens == expect(good)
    assert eng.kv_stats["pages_live"] == 0


def test_inject_fault_validates():
    eng, ex = stub_engine()
    r = Request(prompt=np.arange(4, dtype=np.int32))
    with pytest.raises(InvalidArgError):
        eng.inject_fault(r)             # not submitted yet
    eng.submit(r)
    with pytest.raises(InvalidArgError):
        eng.inject_fault(r, stage="warp-core")
    with pytest.raises(InvalidArgError):
        eng.inject_fault(r, stage="device")   # replica loss: no request
    with pytest.raises(InvalidArgError):
        eng.inject_fault(stage="decode")      # per-request: needs one


# --------------------------------------------------------------------------
# replica-level device loss (mesh failure ladder, docs/mesh.md)
# --------------------------------------------------------------------------

def test_device_loss_fails_all_residents_at_once_typed():
    eng, ex = stub_engine(slots=2)
    rng = np.random.default_rng(7)
    a, b = req(rng, max_new=8), req(rng, max_new=8)
    eng.submit(a)
    eng.submit(b)
    eng.step()                          # both resident, decoding
    eng.inject_fault(stage="device")
    out = eng.step()                    # the loss fires mid-decode
    # every resident failed at once, with the SAME typed error object
    assert {r.id for r in out} == {a.id, b.id}
    assert all(r.state == RequestState.FAILED for r in out)
    assert isinstance(a.error, DeviceLostError) and a.error.code == -2
    assert a.error is b.error is eng.device_lost
    # pages drained to zero on the dead replica
    assert eng.kv_stats["pages_live"] == 0
    assert eng.kv_stats["kv_used_bytes"] == 0


def test_device_loss_leaves_waiting_requests_reclaimable():
    eng, ex = stub_engine(slots=1)
    rng = np.random.default_rng(8)
    resident, queued = req(rng, max_new=8), req(rng, max_new=4)
    eng.submit(resident)
    eng.submit(queued)
    eng.step()
    eng.inject_fault(stage="device")
    eng.step()
    # the engine is terminal: it cannot run the queued work nor accept
    # more — both surface the typed error instead of hanging
    with pytest.raises(DeviceLostError):
        eng.drain()
    with pytest.raises(DeviceLostError):
        eng.submit(req(rng))
    assert eng.step() == []             # terminal: steps are no-ops
    # the waiting request is untouched (no error) and reclaimable for
    # migration; once reclaimed the engine drains empty
    assert queued.error is None
    assert eng.release_waiting() == [queued]
    assert eng.release_waiting() == []
    assert eng.drain() == []


def test_device_loss_on_one_engine_leaves_siblings_unaffected():
    """Regression (ISSUE 9 satellite): a replica-level loss is scoped to
    its engine — requests on a sibling engine sharing the process (and
    the default platform) complete bit-exact."""
    lost_eng, _ = stub_engine(slots=2)
    ok_eng, _ = stub_engine(slots=2)
    rng = np.random.default_rng(9)
    doomed = [req(rng, max_new=6) for _ in range(2)]
    fine = [req(rng, max_new=6) for _ in range(3)]
    for r in doomed:
        lost_eng.submit(r)
    for r in fine:
        ok_eng.submit(r)
    lost_eng.step()
    ok_eng.step()
    lost_eng.inject_fault(stage="device")
    lost_eng.step()
    ok_eng.drain()
    assert all(isinstance(r.error, DeviceLostError) for r in doomed)
    assert all(r.done and r.out_tokens == expect(r) for r in fine)
    assert lost_eng.kv_stats["pages_live"] == 0
    assert ok_eng.kv_stats["pages_live"] == 0


def test_front_submit_runs_before_earlier_arrivals():
    eng, ex = stub_engine(slots=1)
    rng = np.random.default_rng(10)
    first, second, migrated = req(rng), req(rng), req(rng, max_new=2)
    eng.submit(first)
    eng.submit(second)
    eng.submit(migrated, front=True)    # mesh requeue path
    eng.drain()
    # single slot => strict completion order: front-submitted first
    assert migrated.finish_step <= first.finish_step <= second.finish_step
    assert migrated.out_tokens == expect(migrated)


# --------------------------------------------------------------------------
# admission / API
# --------------------------------------------------------------------------

def test_submit_rejects_impossible_prompts():
    eng, ex = stub_engine(slots=2, max_seq=16)
    with pytest.raises(InvalidArgError):
        eng.submit(Request(prompt=np.zeros(0, np.int32)))
    with pytest.raises(InvalidArgError):
        eng.submit(Request(prompt=np.zeros(16, np.int32)))


def test_eos_token_stops_generation():
    eng, ex = stub_engine()
    rng = np.random.default_rng(8)
    r = req(rng, plen=5, max_new=40)
    stream = StubExecutor.expected_tokens(r.prompt, 40)
    r.eos_token = stream[3]             # stop at the 4th token
    eng.submit(r)
    eng.drain()
    assert r.done and r.out_tokens == stream[:4]


def test_fixed_scheduler_is_a_refill_barrier():
    eng, ex = stub_engine(slots=2, scheduler="fixed")
    rng = np.random.default_rng(9)
    reqs = [req(rng, max_new=m) for m in (2, 5, 3)]
    for r in reqs:
        eng.submit(r)
    eng.step()                           # admits exactly the first two
    assert eng.scheduler_stats["running"] == 2
    assert reqs[2].state == RequestState.WAITING
    eng.step()
    eng.step()                           # reqs[0] done; slot stays empty
    assert reqs[0].done
    assert reqs[2].state == RequestState.WAITING, \
        "fixed scheduler refilled before the barrier"
    eng.drain()
    for r in reqs:
        assert r.out_tokens == expect(r)


def test_scheduler_arg_validated():
    with pytest.raises(InvalidArgError):
        ServingEngine(None, None, None, batch_slots=1, max_seq=16,
                      executor=StubExecutor(1, 16), scheduler="magic")
    with pytest.raises(InvalidArgError):
        ServingEngine(None, None, None, batch_slots=2, max_seq=16,
                      executor=StubExecutor(4, 16))   # shape mismatch


# --------------------------------------------------------------------------
# the serve driver surfaces failures
# --------------------------------------------------------------------------

class _FaultyPrefill(StubExecutor):
    """A stub whose prefill fails for prompts starting with token 7."""

    def prefill(self, prompt, slot):
        if int(prompt[0]) == 7:
            raise DeviceLostError("injected prefill fault")
        return super().prefill(prompt, slot)


def test_serve_driver_raises_when_a_request_fails(capsys):
    from repro.launch.serve import RequestsFailed, serve
    ex = _FaultyPrefill(batch_slots=2, max_seq=64)
    eng = ServingEngine(None, None, None, batch_slots=2, max_seq=64,
                        executor=ex)
    reqs = [Request(prompt=np.array([k, 1, 2], np.int32), max_new_tokens=3)
            for k in (5, 7, 9)]
    with pytest.raises(RequestsFailed) as ei:
        serve(eng, reqs)
    assert [int(r.prompt[0]) for r in ei.value.failed] == [7]
    assert isinstance(ei.value.failed[0].error, DeviceLostError)
    assert isinstance(ei.value, ReproError)
    assert "FAILED DeviceLostError" in capsys.readouterr().out
    for r in (reqs[0], reqs[2]):
        assert r.done and r.out_tokens == expect(r)


def test_serve_driver_returns_retired_requests():
    from repro.launch.serve import serve
    eng, ex = stub_engine(slots=2)
    rng = np.random.default_rng(11)
    reqs = [req(rng, max_new=3) for _ in range(4)]
    done = serve(eng, reqs, arrival_every=2)
    assert sorted(r.id for r in done) == sorted(r.id for r in reqs)
    assert all(r.done and r.out_tokens == expect(r) for r in reqs)
