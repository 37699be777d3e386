"""Open-loop serving of a dense decoder through the program's serving path.

The system under test is ``repro.serving.ServingEngine`` over its default
``JaxExecutor``: ``submit`` puts a request in the admission queue and each
``step`` runs one scheduler step (prefills and the decode step as commands
on the runtime's out-of-order ``CommandQueue``).  One host thread offers
load and steps the engine: requests are submitted as they fall due on a
Poisson schedule (:func:`bench.traffic.open_loop`), and each token is
stamped when the host sees it after a step.

Set-up: weights from the seed on the device, the engine, and one request
per prefill bucket the traffic uses (which also runs insert and decode),
then ``preroll_s`` seconds of load so the window starts in steady state.
The window's requests are those due in it.  The run goes on, at the same
offered load, until each of them has its first token (``drain_cap_s`` at
most), and reports the TTFT tail over all of them and the tail of every
inter-token gap that ended inside the window.

``correct``: a sample of finished requests drawn from the seed, with the
longest one in it, goes through the float32 reference
(:mod:`bench.reference.dense_lm`) over its prompt and served tokens, once
the program's state is freed; the widest gap by which a served token's
logit lies below the reference's best is held to the cell's limit.  With
``ctx.control`` (``bench/control.py``) the float8 control's tokens are
judged in the served tokens' place, so the run reads ``correct: false``
where the limit catches the control.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Dict, List

import numpy as np


def model_config(cfg: Dict):
    """The program's ModelConfig for the configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["dtypes"]["compute"], param_dtype=cfg["dtypes"]["params"])


class Rec:
    """One offered request and what the host saw of it."""
    __slots__ = ("arr", "req", "due", "tokens", "seen", "end")

    def __init__(self, arr, req, due):
        self.arr, self.req, self.due = arr, req, due
        self.tokens: List[float] = []     # host time of each token
        self.seen = 0
        self.end = None                   # host time it finished or failed


def _sample(recs: List[Rec], k: int, seed: int) -> List[Rec]:
    """``k`` finished requests drawn from the seed, the longest included."""
    ok = [r for r in recs if r.req.done and r.req.error is None]
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r.req.out_tokens))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def live_positions(recs: List[Rec], t0: float, t1: float) -> float:
    """Time-average over ``[t0, t1)`` of the cache positions the admitted
    requests hold (prompt plus tokens so far), from the host's stamps."""
    area = 0.0
    for r in recs:
        stamps = r.tokens + [t1 if r.end is None else r.end]
        held = len(r.arr.prompt)
        for j in range(len(r.tokens)):
            a, b = max(stamps[j], t0), min(stamps[j + 1], t1)
            if b > a:
                area += (b - a) * (held + j + 1)
    return area / (t1 - t0)


def run(ctx):
    H = ctx.harness
    cfg, wl = ctx.cell.config, ctx.cell.workload
    tr, srv = wl["mix"], cfg["serving"]
    from bench import traffic, weights
    from bench.counts import dense_lm as counts
    from bench.reference import dense_lm as reference
    from repro.distributed.sharding import BASELINE_RULES
    from repro.runtime import Context
    from repro.serving import Request, ServingEngine

    span = H.span
    counter = H.CompileCounter()
    gcw = H.GcWatch()
    V, S = int(cfg["vocab_size"]), int(srv["max_seq"])
    params = weights.dense_lm(cfg, ctx.seed, ctx.devices[0])
    eng = ServingEngine(model_config(cfg), params, BASELINE_RULES,
                        batch_slots=int(srv["slots"]), max_seq=S,
                        context=Context(),
                        prefill_bucket=int(srv["prefill_bucket"]))
    del params

    # warm exactly the traffic's prefill buckets (a prompt one short of
    # each power of two pads to it), plus insert and decode
    rng = np.random.default_rng(ctx.seed)
    warm = [Request(prompt=rng.integers(0, V, b - 1).astype(np.int32),
                    max_new_tokens=2) for b in wl["warm_buckets"]]
    eng.generate(warm)
    bad = [r for r in warm if not r.done]
    if bad:
        raise RuntimeError(f"{len(bad)} warm-up requests failed: "
                           f"{bad[0].error!r}")

    rate = float(tr["rate_rps"])
    arrivals = traffic.open_loop(tr, rate, ctx.seconds, V, ctx.seed)
    drain_cap = float(tr["drain_cap_s"])
    prof = H.Profiler(ctx.trace)
    trace_s = min(float(wl["trace"]["seconds"]), ctx.seconds)

    recs: List[Rec] = []
    queued: deque = deque()          # submitted, still waiting
    running: List[Rec] = []          # admitted, not finished
    counters0 = counters1 = None
    backlog = {}
    origin = time.perf_counter() + float(tr["preroll_s"])
    t_end = origin + ctx.seconds
    dues = [origin + a.due for a in arrivals]
    i, n = 0, len(arrivals)
    timed: List[Rec] = []
    late: List[float] = []
    longest_turn = 0.0
    errors: List[str] = []
    while True:
        now = time.perf_counter()
        if now >= origin and not counter.armed and "start" not in backlog:
            counter.armed = gcw.armed = True
            backlog["start"] = len(queued)
        if ctx.trace:
            if now >= origin and not prof.active and not prof.done:
                prof.start()
                counters0 = eng.compile_stats
            elif prof.active and now >= prof.t0 + trace_s:
                counters1 = eng.compile_stats
                prof.stop()
        while i < n and dues[i] <= now:
            a = arrivals[i]
            req = Request(prompt=a.prompt, max_new_tokens=a.max_new)
            with span("bench.submit"):
                eng.submit(req)
            rec = Rec(a, req, dues[i])
            recs.append(rec)
            queued.append(rec)
            if a.block == "window":
                timed.append(rec)
                late.append(now - dues[i])
            i += 1
        if now >= t_end and "end" not in backlog:
            backlog["end"] = len(queued)
        if now >= t_end and \
                all(r.tokens or r.end is not None for r in timed):
            break
        if now >= t_end + drain_cap or (i >= n and not queued
                                        and not running):
            break
        if not queued and not running:
            with span("bench.wait"):
                time.sleep(max(0.0, min(dues[i] - now, 0.01)))
            continue
        with span("bench.step"):
            eng.step()
        t = time.perf_counter()
        if counter.armed:
            longest_turn = max(longest_turn, t - now)
        while queued and queued[0].req.state != "waiting":
            running.append(queued.popleft())
        still = []
        for r in running:
            got = len(r.req.out_tokens)
            if got < r.seen:
                errors.append(f"request {r.req.id} was preempted")
                r.seen = 0
            if got > r.seen:
                r.tokens.extend([t] * (got - r.seen))
                r.seen = got
            if r.req.done or r.req.error is not None:
                r.end = t
            else:
                still.append(r)
        running = still
    counter.armed = gcw.armed = False
    gcw.close()
    if prof.active:
        counters1 = eng.compile_stats
        prof.stop()

    peak = H.memory_peak_bytes(ctx.devices)
    eng = None
    gc.collect()

    ttft = [(r.tokens[0] - r.due) if r.tokens else float("inf")
            for r in timed]
    itl = [b - a for r in recs for a, b in zip(r.tokens, r.tokens[1:])
           if origin <= b < t_end]
    completed = [r for r in recs if r.end is not None
                 and origin <= r.end < t_end and r.req.error is None]
    e2e = {}
    if ttft and all(np.isfinite(ttft)):
        e2e["ttft_p95_ms"] = 1e3 * H.percentile(ttft, 95)
    if itl:
        e2e["itl_p95_ms"] = 1e3 * H.percentile(itl, 95)
    attempted = len(timed)
    failed = sum(1 for r in timed if not r.tokens or r.req.error is not None)
    judged = [r for r in recs if r.end is not None]
    third = max(1, len(timed) // 3)
    finite = [x for x in ttft if np.isfinite(x)]
    print(f"generator: {len(late)} window arrivals at {rate:.3f} req/s, "
          f"lateness p50 {1e3 * H.percentile(late or [0], 50):.3f} ms, "
          f"p99 {1e3 * H.percentile(late or [0], 99):.3f} ms, "
          f"max {1e3 * max(late or [0]):.3f} ms", flush=True)
    print(f"window: compiles {counter.compiles}, traces {counter.traces}; "
          f"waiting queue {backlog.get('start')} at start, "
          f"{backlog.get('end')} at end; completed {len(completed)}",
          flush=True)
    print(f"host: longest turn of the loop {1e3 * longest_turn:.1f} ms; "
          f"garbage collections of generations 0/1/2: "
          f"{'/'.join(map(str, gcw.count))}, longest "
          f"{'/'.join(f'{1e3 * x:.1f}' for x in gcw.longest)} ms",
          flush=True)
    sweep = {
        "rate_rps": rate, "queue_start": backlog.get("start"),
        "queue_end": backlog.get("end"),
        "ttft_first_third_ms": 1e3 * H.percentile(finite[:third] or [0], 50),
        "ttft_last_third_ms": 1e3 * H.percentile(finite[-third:] or [0], 50),
        "no_first_token": sum(1 for r in timed if not r.tokens),
        "served_tokens_per_s": sum(len(r.arr.prompt) + len(r.req.out_tokens)
                                   for r in completed) / ctx.seconds,
        **e2e}
    print("sweep: " + repr(sweep), flush=True)
    live = live_positions(recs, origin, t_end)
    slots = int(srv["slots"])
    print(f"kv: live positions {live:.1f} of {slots * S} "
          f"({100 * live / (slots * S):.2f} %), "
          f"{counts.kv_bytes(cfg, live) / 1e9:.4f} GB of "
          f"{counts.kv_bytes(cfg, slots * S) / 1e9:.4f} GB", flush=True)
    if counter.compiles:
        errors.append(f"{counter.compiles} compiles inside the window")

    # the check, once the window has closed and the program is freed
    t_check = time.perf_counter()
    sample = _sample(judged, int(wl["check"]["sample_requests"]), ctx.seed)
    ref_params = weights.dense_lm(cfg, ctx.seed, ctx.devices[0])
    gaps, cgaps, n_tok = [], [], 0
    for r in sample:
        res = reference.served_gaps(ref_params, cfg, r.arr.prompt,
                                    r.req.out_tokens, S,
                                    control=ctx.control)
        gaps.append(float(np.max(res["gaps"])))
        n_tok += len(res["gaps"])
        if "control_gaps" in res:
            cgaps.append(float(np.max(res["control_gaps"])))
    if not sample:
        errors.append("no finished request to check")
    print(f"check: {len(sample)} requests, {n_tok} served tokens compared "
          f"in {time.perf_counter() - t_check:.1f} s", flush=True)
    judged_gaps = cgaps if ctx.control else gaps
    checks = [H.Check("max_logit_gap",
                      max(judged_gaps) if judged_gaps else float("inf"),
                      float(wl["check"]["limits"]["max_logit_gap"]))]

    summary = prof.summary() if ctx.trace else None
    obs = {}
    if summary is not None:
        t0, t1 = prof.t0, prof.t1
        # the position each token decoded in the traced stretch sits at
        decode_pos = [len(r.arr.prompt) + j - 1 for r in recs
                      for j, t in enumerate(r.tokens) if j and t0 <= t <= t1]
        obs = {"trace": summary, "cfg": cfg,
               "device_kind": ctx.devices[0].device_kind,
               "decode_positions": decode_pos,
               "counters": {k: counters1[k] - counters0[k]
                            for k in ("decode_steps", "prefill_calls")}
               if counters0 and counters1 else None}
    out = H.Outcome(end_to_end=e2e, setup_s=origin - ctx.process_t0,
                    attempted=attempted, failed=failed, checks=checks,
                    devices=ctx.devices, memory_peak_bytes=peak,
                    trace=summary, obs=obs, errors=errors)
    out.obs.update(gaps=gaps, control_gaps=cgaps, sweep=sweep,
                   live_positions=live)
    return out
