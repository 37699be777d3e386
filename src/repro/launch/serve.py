"""Continuous-batching serving driver (smoke-scale on CPU, production
mesh on TPU).

Requests are submitted into the engine's admission queue on a staggered
arrival schedule and the driver pumps ``step()`` until the queue drains —
the submit()/step() loop a real serving front-end runs, exercising
per-step slot refill and paged KV instead of one-shot batch generate.
A request that ends FAILED raises :class:`RequestsFailed` after the
report is printed, so the process exits non-zero.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 6 --max-new 16
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import numpy as np

from repro import configs
from repro.core.errors import ReproError
from repro.distributed.sharding import BASELINE_RULES
from repro.models import init_params
from repro.runtime import Context
from repro.serving import Request, ServingEngine


class RequestsFailed(ReproError, RuntimeError):
    """At least one served request ended FAILED; ``failed`` holds them,
    each with its typed ``error``."""

    def __init__(self, failed: List[Request]):
        super().__init__(
            f"{len(failed)} request(s) failed: " + ", ".join(
                f"req{r.id} {type(r.error).__name__}: {r.error}"
                for r in failed))
        self.failed = failed


def serve(eng: ServingEngine, reqs: List[Request], arrival_every: int = 1,
          trace: Optional[str] = None) -> List[Request]:
    """Submit ``reqs`` one every ``arrival_every`` scheduler steps, pump
    the engine until it drains, and print the report.  Returns the
    retired requests; raises :class:`RequestsFailed` when any failed."""
    t0 = time.time()
    done: List[Request] = []
    pending = list(reqs)
    # staggered arrivals, then pump the scheduler until the queue drains —
    # optionally recording every DAG command (plus a kv_pages_live
    # counter track) as a Chrome trace
    ctx = eng.context
    with ctx.trace() as tr:
        while pending or eng.scheduler_stats["waiting"] or \
                eng.scheduler_stats["running"]:
            if pending and eng.current_step % max(1, arrival_every) == 0:
                eng.submit(pending.pop(0))
            done.extend(eng.step())
            if trace:
                tr.counter("kv_pages_live", eng.kv_stats["pages_live"],
                           process="serve")
    dt = time.time() - t0
    if trace:
        doc = tr.export(trace)
        print(f"trace: {len(doc['traceEvents'])} events -> {trace} "
              f"(load in chrome://tracing)")

    total_toks = sum(len(r.out_tokens) for r in done if r.done)
    print(f"served {len(done)} requests, {total_toks} tokens "
          f"in {dt:.2f}s ({total_toks / max(dt, 1e-9):.1f} tok/s wall "
          f"clock, compiles included: a smoke figure, not a measurement)")
    sched = eng.scheduler_stats
    print(f"  sched: {sched['steps']} steps, {sched['evictions']} "
          f"evictions, {sched['preemptions']} preemptions, "
          f"{sched['failed']} failed")
    dag = eng.dag_stats
    if dag["steps"]:
        print(f"  dag: {dag['events']} events over {dag['steps']} steps, "
              f"overlap {dag['overlap']:.2f}x")
    kv = eng.kv_stats
    print(f"  kv pool: {kv['hits']} hits / {kv['misses']} misses, "
          f"{kv['page_bytes']} B/page x {kv['pages_live']} live, "
          f"{kv['frees']} frees (context pools: {list(ctx.pool_stats())})")
    for r in done:
        tag = "FAILED " + type(r.error).__name__ if r.error else \
            f"{r.out_tokens}"
        print(f"  req{r.id}: prompt[:4]={r.prompt[:4].tolist()} -> {tag}")
    failed = [r for r in done if r.error is not None]
    if failed:
        raise RequestsFailed(failed)
    return done


def main(argv=None):
    """Build the engine for ``--arch`` and serve ``--requests`` random
    prompts.  Returns ``(engine, retired requests)``; raises
    :class:`RequestsFailed` when any request failed."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=16,
                    help="prompt lengths are drawn from [4, MAX_PROMPT]")
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", choices=["continuous", "fixed"],
                    default="continuous")
    ap.add_argument("--arrival-every", type=int, default=1,
                    help="submit one request every N scheduler steps")
    ap.add_argument("--trace", metavar="OUT.JSON", default=None,
                    help="export the run's event DAG as Chrome-trace "
                         "JSON (open in chrome://tracing, docs/mesh.md)")
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    rng = np.random.default_rng(args.seed)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    aux = {}
    if cfg.family == "vlm":
        aux["img_embeds"] = np.asarray(rng.standard_normal(
            (args.batch_slots, cfg.n_img_tokens, cfg.d_model)), np.float32)
    if cfg.family == "encdec":
        aux["frames"] = np.asarray(rng.standard_normal(
            (args.batch_slots, cfg.enc_seq, cfg.d_model)), np.float32)

    # the engine's dispatch queue and KV page pool come from a host
    # Context (docs/host_api.md) — the same object model kernel launches
    # and co-execution use
    ctx = Context()
    eng = ServingEngine(cfg, params, BASELINE_RULES,
                        batch_slots=args.batch_slots, max_seq=args.max_seq,
                        aux_inputs=aux, context=ctx,
                        scheduler=args.scheduler)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                        rng.integers(4, args.max_prompt + 1),
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, args.max_new + 1)))
            for _ in range(args.requests)]
    return eng, serve(eng, reqs, arrival_every=args.arrival_every,
                      trace=args.trace)


if __name__ == "__main__":
    from repro.backend import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    main()
