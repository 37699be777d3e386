"""Smoke run of both halves of the system on one TPU chip.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the multi-chip path only

Everything runs in this one process, which holds the chip.  The phases,
each printing one result line:

* ``suite``  — the six DSL suite kernels through Context -> Program ->
  Kernel -> ``ctx.launch`` on the chip's ``vector`` device at their
  ``full`` shapes, and stencil1d and scan at 128 MiB per buffer, each
  bitwise-equal to its NumPy oracle (``repro.suite.oracles``);
* ``pallas`` — each suite kernel on the ``pallas`` device: either it
  compiles (and is then bitwise) or Mosaic refuses it with the typed
  ``BuildError``; an interpreted run is a failure;
* ``kernels`` — flash_attention, decode_attention and rmsnorm compiled
  through Mosaic at smollm-135m widths, within bf16 tolerance of
  ``repro.kernels.ref``;
* ``serve``  — ``repro.launch.serve.main`` on the full smollm-135m config
  (30 layers, d_model 576, vocab 49152, random weights from a seed):
  48 requests over 32 slots x 4096 cache positions, none failing, and a
  handful served again one at a time with equal token streams.

With ``--four-chips`` only the multi-chip path runs: a ``CoExecutor``
launch of the 128 MiB stencil1d over four devices bound to four chips
(bitwise-equal to one device), and a ``ServingMesh`` of four one-chip
smollm-135m replicas whose streams equal one replica's serial streams.

The last line of the output is one JSON object naming the device; it is
printed only when every phase passed.  The script exits non-zero when a
phase fails and when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
SLOTS, MAX_SEQ = 32, 4096          # a 3.0 GB KV cache
N_REQUESTS, MAX_NEW, MAX_PROMPT = 48, 32, 64   # prefill buckets 8..64
N_SERIAL = 4
BIG_N = 32 * 2 ** 20               # float32 elements: 128 MiB per buffer
# the vector target runs work-groups one after another, so the 128 MiB
# runs use the largest work-group (1024 work-items) to keep the group
# count at 32768, and the unrolled scan: a DSL while loop carries every
# global buffer through a lax.while_loop that copies them (3 x 128 MiB
# per work-group in the compiled program)
BIG_RUNS = (("stencil1d", {"n": BIG_N}, {"lsz": 1024, "use_local": 0}),
            ("scan", {"n": BIG_N, "seg": 1024}, {"unroll": 1}))
BF16_TOL = 3e-2


def _check(ok, what) -> None:
    """Fail the phase (under ``python -O`` too) unless ``ok``."""
    if not ok:
        raise RuntimeError(what)


def _suite_launch(ctx, device, sk, shape, params):
    """Launch suite kernel ``sk`` at ``shape`` through Context -> Program
    -> Kernel -> ``ctx.launch``; returns (bitwise, seconds, MiB/buffer)."""
    inputs = sk.make_inputs(shape, params)
    expected = sk.oracle(inputs, shape, params)
    gsz, lsz = sk.launch_dims(shape, params)
    kern = ctx.create_program(sk.build(shape, params)).create_kernel()
    kern.set_args(**inputs)
    t0 = time.perf_counter()
    out = ctx.launch(kern, gsz, lsz, device=device)
    dt = time.perf_counter() - t0
    ok = all(out[n].tobytes() == expected[n].tobytes() for n in sk.outputs)
    return ok, dt, max(a.nbytes for a in inputs.values()) / 2 ** 20


def phase_suite(ctx, vec):
    from repro.suite.kernels import SUITE, suite_kernels
    cases = [(sk.name, sk, sk.shapes["full"], sk.space(sk.shapes["full"])[0])
             for sk in suite_kernels()]
    cases += [(f"{name}@128MiB", SUITE[name], shape, params)
              for name, shape, params in BIG_RUNS]
    lines, bad = [], []
    for label, sk, shape, params in cases:
        ok, dt, mib = _suite_launch(ctx, vec, sk, shape, params)
        lines.append(f"{label}={'bitwise' if ok else 'MISMATCH'}"
                     f"({mib:.1f}MiB,{dt:.2f}s incl. compile)")
        print(f"  suite/vector {lines[-1]}", flush=True)
        if not ok:
            bad.append(label)
    print("suite/vector: " + " ".join(lines), flush=True)
    _check(not bad, f"suite kernels not bitwise on vector: {bad}")


def phase_pallas(ctx, dev):
    from repro.core import BuildError
    from repro.suite.kernels import suite_kernels
    lines, bad = [], []
    for sk in suite_kernels():
        shape = sk.shapes["full"]
        params = sk.space(shape)[0]
        inputs = sk.make_inputs(shape, params)
        expected = sk.oracle(inputs, shape, params)
        gsz, lsz = sk.launch_dims(shape, params)
        kern = ctx.create_program(sk.build(shape, params)).create_kernel()
        kern.set_args(**inputs)
        if kern.bind(dev, lsz).prog.interpret:
            bad.append(f"{sk.name} would run interpreted")
            continue
        try:
            out = ctx.launch(kern, gsz, lsz, device=dev)
        except BuildError as e:
            log = e.build_log.strip().splitlines()
            lines.append(f"{sk.name}=refused({e.code_name}: "
                         f"{log[0] if log else e})")
            print(f"  suite/pallas {lines[-1]}", flush=True)
            continue
        ok = all(out[n].tobytes() == expected[n].tobytes()
                 for n in sk.outputs)
        lines.append(f"{sk.name}=compiled,{'bitwise' if ok else 'MISMATCH'}")
        print(f"  suite/pallas {lines[-1]}", flush=True)
        if not ok:
            bad.append(f"{sk.name} compiled but not bitwise")
    print("suite/pallas: " + " ; ".join(lines), flush=True)
    _check(not bad, "; ".join(bad))


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.backend import pallas_interpret
    from repro.kernels import ops, ref

    _check(not pallas_interpret(), "Pallas would run in interpret mode")
    cfg = configs.get_config(ARCH)
    H, KV, D, d = cfg.n_heads, cfg.n_kv, cfg.hd, cfg.d_model
    rng = np.random.default_rng(0)

    def rnd(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    S = 256
    lengths = jnp.asarray(rng.integers(1, MAX_SEQ + 1, SLOTS), jnp.int32)
    cases = {
        "flash_attention": (
            lambda q, k, v: ops.attention(q, k, v, causal=True,
                                          use_pallas=True),
            lambda q, k, v: ref.attention(q, k, v, causal=True),
            (rnd((1, H, S, D)), rnd((1, KV, S, D)), rnd((1, KV, S, D)))),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                    use_pallas=True),
            ref.decode_attention,
            (rnd((SLOTS, H, D)), rnd((SLOTS, KV, MAX_SEQ, D)),
             rnd((SLOTS, KV, MAX_SEQ, D)), lengths)),
        "rmsnorm": (
            lambda x, w: ops.rmsnorm(x, w, use_pallas=True),
            ref.rmsnorm,
            (rnd((S, d)), rnd((d,), jnp.float32))),
    }
    lines, bad = [], []
    for name, (fn, want_fn, args) in cases.items():
        compiled = jax.jit(fn).lower(*args).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(jax.jit(want_fn)(*args), np.float32)
        # allclose passes where this ratio is <= 1
        err = float(np.max(np.abs(got - want)
                           / (BF16_TOL * (1 + np.abs(want)))))
        ok = mosaic and np.all(np.isfinite(got)) and \
            np.allclose(got, want, atol=BF16_TOL, rtol=BF16_TOL)
        lines.append(f"{name}={'ok' if ok else 'FAIL'}"
                     f"(mosaic={mosaic},shape={tuple(got.shape)},"
                     f"err/tol={err:.3f})")
        print(f"  kernels {lines[-1]}", flush=True)
        if not ok:
            bad.append(name)
    print("kernels: " + " ".join(lines), flush=True)
    _check(not bad, f"model kernels failed: {bad}")


def _replay(eng, reqs):
    """Serve copies of ``reqs`` one at a time; returns their streams."""
    from repro.serving import Request
    out = []
    for r in reqs:
        again = Request(prompt=r.prompt.copy(),
                        max_new_tokens=r.max_new_tokens)
        eng.generate([again])
        _check(again.done and again.error is None, again.error)
        out.append(again.out_tokens)
    return out


def phase_serve(cache_dir):
    import jax

    from repro.launch import serve

    eng, done = serve.main([
        "--arch", ARCH, "--requests", str(N_REQUESTS),
        "--max-new", str(MAX_NEW), "--max-prompt", str(MAX_PROMPT),
        "--batch-slots", str(SLOTS), "--max-seq", str(MAX_SEQ)])
    _check(len(done) == N_REQUESTS and all(r.done for r in done),
           f"{sum(r.done for r in done)} of {N_REQUESTS} requests finished")
    buckets = {}
    for r in done:
        buckets.setdefault(eng._exec.bucket(len(r.prompt)), r)
    picks = list(buckets.values())[:N_SERIAL]
    serial = _replay(eng, picks)
    same = [s == r.out_tokens for s, r in zip(serial, picks)]
    stats = jax.devices()[0].memory_stats() or {}
    print(f"serve: {len(done)} requests, 0 failed, prefill buckets "
          f"{sorted(buckets)}, serial replay of {len(picks)} equal="
          f"{all(same)}, compile_stats={eng.compile_stats}, "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}, "
          f"bytes_limit={stats.get('bytes_limit')}, "
          f"compile_cache={cache_dir}", flush=True)
    _check(all(same), f"serial streams differ: {same}")


def _chip_bytes():
    import jax
    stats = [(str(d), d.memory_stats() or {}) for d in jax.devices()]
    return [(name, s.get("bytes_in_use"), s.get("peak_bytes_in_use"))
            for name, s in stats]


def phase_four_coexec(ctx):
    from repro.suite.kernels import SUITE
    name, shape, params = BIG_RUNS[0]
    sk = SUITE[name]
    inputs = sk.make_inputs(shape, params)
    expected = sk.oracle(inputs, shape, params)
    gsz, lsz = sk.launch_dims(shape, params)
    kern = ctx.create_program(sk.build(shape, params)).create_kernel()
    kern.set_args(**inputs)
    devs = ctx.platform.co_devices(4)
    co = ctx.create_co_executor(devs).launch(kern, gsz, lsz)
    one = ctx.launch(kern, gsz, lsz, device=devs[0])
    ok = co["y"].tobytes() == one["y"].tobytes() == \
        expected["y"].tobytes()
    print(f"four/coexec: stencil1d@128MiB over "
          f"{[str(d.jax_device) for d in devs]} bitwise_vs_one_device={ok} "
          f"chips(bytes_in_use,peak)={_chip_bytes()}", flush=True)
    _check(len({d.jax_device for d in devs}) == 4, "devices share a chip")
    _check(ok, "co-executed stencil1d differs from one device")


def phase_four_mesh():
    import jax
    import numpy as np

    from repro import configs
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import init_params
    from repro.serving import Request, ServingMesh

    cfg = configs.get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = ServingMesh(cfg, params, BASELINE_RULES, n_replicas=4,
                       batch_slots=SLOTS, max_seq=MAX_SEQ)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, rng.integers(4, 9))
                    .astype(np.int32), max_new_tokens=MAX_NEW)
            for _ in range(16)]
    for r in reqs:
        mesh.submit(r)
    done = mesh.drain()
    _check(len(done) == len(reqs) and all(r.done for r in reqs),
           "mesh left requests unfinished")
    serial = _replay(mesh.replicas[0].engine, reqs)
    same = all(s == r.out_tokens for s, r in zip(serial, reqs))
    bound = [str(r.device.jax_device) for r in mesh.replicas]
    print(f"four/mesh: {len(done)} requests over replicas {bound}, "
          f"steps per replica {[r.steps for r in mesh.replicas]}, "
          f"streams equal one replica's serial streams={same}, "
          f"chips(bytes_in_use,peak)={_chip_bytes()}", flush=True)
    _check(len(set(bound)) == 4, "replicas share a chip")
    _check(same, "mesh streams differ from serial")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip co-execution and mesh")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    import jax
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev0.platform}); nothing "
              f"was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.backend import enable_compile_cache
    from repro.runtime import Context
    cache_dir = enable_compile_cache()
    ctx = Context()
    if args.four_chips:
        phases = [("four/coexec", lambda: phase_four_coexec(ctx)),
                  ("four/mesh", phase_four_mesh)]
    else:
        vec = ctx.platform.get_devices("vector")[0]
        pal = ctx.platform.get_devices("pallas")[0]
        phases = [("suite/vector", lambda: phase_suite(ctx, vec)),
                  ("suite/pallas", lambda: phase_pallas(ctx, pal)),
                  ("kernels", phase_kernels),
                  ("serve", lambda: phase_serve(cache_dir))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:
            traceback.print_exc()
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"{name}: phase took {time.perf_counter() - t0:.1f}s",
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
