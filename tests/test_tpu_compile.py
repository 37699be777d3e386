"""Compile rehearsals for one TPU v5e chip, run without the chip.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described rather than attached (``jax.experimental.topologies``).
These tests compile the main path at its real widths and check what only
the chip's compiler can refuse: Mosaic's tiling rules for the Pallas
kernels, and whether the full-width smollm-135m decode step fits HBM.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture (never while a
module is imported), so every test worker collects the same tests and
only the worker given this file loads the TPU library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.distributed.sharding import BASELINE_RULES
from repro.kernels import kv_cache
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import abstract_params, init_caches
from repro.serving.executor import step_functions
from repro.suite.kernels import SUITE
from repro.core.api import _compile_kernel

#: v5e HBM as its compiler counts it ("Used ... of 15.75G hbm")
HBM_BYTES = int(15.75 * 2 ** 30)
#: chip_smoke.py serves at this batch of slots x cache length
SLOTS, MAX_SEQ = 32, 4096
#: chip_smoke.py's large suite runs: 32 Mi float32 = 128 MiB per buffer
BIG_N = 32 * 2 ** 20

SMOLLM = configs.get_config("smollm-135m")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _model_kernel_case(name, sh):
    """(function, abstract args) of a Pallas kernel at smollm widths,
    compiled (``interpret=False``) as the TPU runs it."""
    H, KV, D, d = SMOLLM.n_heads, SMOLLM.n_kv, SMOLLM.hd, SMOLLM.d_model
    bf16 = jnp.bfloat16
    if name == "flash_attention":
        S = 256                         # a prefill bucket
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                (_spec((1, H, S, D), bf16, sh), _spec((1, KV, S, D), bf16, sh),
                 _spec((1, KV, S, D), bf16, sh)))
    if name == "decode_attention":
        return (lambda q, k, v, n: decode_attention(q, k, v, n,
                                                    interpret=False),
                (_spec((SLOTS, H, D), bf16, sh),
                 _spec((SLOTS, KV, MAX_SEQ, D), bf16, sh),
                 _spec((SLOTS, KV, MAX_SEQ, D), bf16, sh),
                 _spec((SLOTS,), jnp.int32, sh)))
    stack = _spec((SMOLLM.n_layers, SLOTS, KV, D, MAX_SEQ), bf16, sh)
    lengths = _spec((SLOTS,), jnp.int32, sh)
    if name == "layer_decode_attention":
        return (lambda q, k, v, n: kv_cache.decode_attention(
                    q, k, v, n, 7, interpret=False),
                (_spec((SLOTS, H, D), bf16, sh), stack, stack, lengths))
    if name == "cache_write":
        new = _spec((SLOTS, KV, D), bf16, sh)
        return (lambda k, v, kn, vn, n: kv_cache.cache_write(
                    k, v, kn, vn, n, 7, interpret=False),
                (stack, stack, new, new, lengths))
    return (lambda x, w: rmsnorm(x, w, interpret=False),
            (_spec((SLOTS, d), bf16, sh), _spec((d,), jnp.float32, sh)))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "layer_decode_attention", "cache_write",
                                  "rmsnorm"])
def test_model_kernel_compiles_to_mosaic(one_chip, name):
    fn, args = _model_kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: an HLO instruction that makes a new array: ``%name = dtype[dims]{layout} op(``
_HLO_ARRAY_OP = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} "
    r"(copy|scatter|dynamic-update-slice)\(")


def _array_ops(hlo: str):
    """(op, bytes, line) of every copy, scatter and dynamic-update-slice
    of the compiled module."""
    for line in hlo.splitlines():
        m = _HLO_ARRAY_OP.match(line)
        if m:
            n = math.prod(int(d) for d in m.group(2).split(",") if d)
            bits = int(re.sub(r"\D", "", m.group(1)) or 8)   # bf16, pred
            yield m.group(3), n * bits // 8, line


@pytest.mark.parametrize("slots,max_seq", [
    (SLOTS, MAX_SEQ),
    (64, 2048),                       # the benchmark's chat cell
])
def test_full_width_decode_step_fits_hbm(one_chip, slots, max_seq):
    """The smollm-135m decode step at full width: the compiler accepts it,
    its footprint fits one chip's HBM, and it keeps the KV cache where it
    lies: the donated cache aliases the result, the temporaries stay
    under a tenth of the cache, and no copy, scatter or
    dynamic-update-slice makes an array as large as one layer's K (the
    per-step cast of the float32 weights, ``params[...]``, aside)."""
    cfg = SMOLLM
    put = lambda t: jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    params = put(abstract_params(cfg))
    caches = put(init_caches(cfg, slots, max_seq, abstract=True))
    toks = _spec((slots, 1), jnp.int32, one_chip)
    occupied = _spec((slots,), jnp.bool_, one_chip)
    decode = step_functions(cfg, BASELINE_RULES, {})[2]
    compiled = decode.lower(params, toks, caches, occupied).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(caches))
    assert used >= cache_bytes
    assert used < HBM_BYTES, f"decode step needs {used} B of {HBM_BYTES}"
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / 10, mem
    layer_k = caches["k"].size * caches["k"].dtype.itemsize // cfg.n_layers
    big = [line for op, n, line in _array_ops(compiled.as_text())
           if n >= layer_k and 'op_name="params[' not in line]
    assert not big, big


@pytest.mark.parametrize("name,shape,params", [
    ("stencil1d", {"n": BIG_N}, {"lsz": 1024, "use_local": 0}),
    ("scan", {"n": BIG_N, "seg": 1024}, {"unroll": 1}),
])
def test_suite_kernel_compiles_on_vector_target(one_chip, name, shape,
                                                params):
    """The DSL's vector target lowers through XLA at chip_smoke's
    128 MiB-per-buffer runs."""
    sk = SUITE[name]
    gsz, lsz = sk.launch_dims(shape, params)
    kern = _compile_kernel(sk.build(shape, params), lsz, target="vector",
                           cache=False)
    n = shape["n"]
    bufs = {"x": _spec((n,), jnp.float32, one_chip),
            "y": _spec((n,), jnp.float32, one_chip)}
    compiled = jax.jit(
        lambda b: kern.prog.run_ndrange(b, {}, gsz)).lower(bufs).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * 4 * n
    assert mem.temp_size_in_bytes < HBM_BYTES
