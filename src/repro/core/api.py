"""Kernel-compiler entry point (the layer under the host object model).

``_compile_kernel(build, local_size, target=...)`` runs the full
pocl-style pipeline at *enqueue* time (the paper specializes the
work-group function per local size, §4.1) and returns a callable
compiled kernel.  Host code reaches it through
:class:`~repro.core.program.Program` /
:class:`~repro.runtime.context.Context` (docs/host_api.md); the public
``compile_kernel`` wrapper survives as a deprecated shim.

Targets:
  ``vector``  — work-items on lanes, if-converted divergence (SIMD mapping)
  ``loop``    — serial work-item loops ('basic' driver analogue)
  ``pallas``  — vector mapping wrapped in a ``pl.pallas_call`` (Mosaic on
                the TPU, interpret mode only on the CPU)
  ``auto``    — target chosen per kernel shape by the autotuner
                (:mod:`repro.core.autotune`)

``build`` is a zero-argument function returning a fresh
:class:`repro.core.ir.Function` (the pipeline mutates the CFG, and one
work-group function is generated per local size).  Compilation is memoized
in a content-addressed :class:`repro.core.cache.CompilationCache` keyed by
the canonical IR hash + specialization parameters, so re-enqueueing an
identical kernel is a hash lookup, not a pipeline re-run (docs/caching.md).
Pass ``cache=False`` to force a fresh compile, or a ``CompilationCache``
instance to use a private cache (each runtime ``Device`` owns one).
"""

from __future__ import annotations

import threading
import warnings
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import numpy as np

from .cache import CacheKey, CompilationCache, PlanKey, default_cache, ir_hash
from .errors import InvalidArgError
from .ir import Function
from .passes import WorkGroupPlan, build_plan
from .targets.loop import LoopWGProgram
from .targets.vector import WGProgram

# running count of actual pipeline executions (cache misses); tests and
# bench_cache use it to prove steady-state launches do zero compile work.
# Guarded: compiles run concurrently on CommandQueue worker threads.
_compiles_done = 0
_compiles_lock = threading.Lock()


def compile_count() -> int:
    with _compiles_lock:
        return _compiles_done


class CompiledKernel:
    def __init__(self, prog: WGProgram, name: str):
        self.prog = prog
        self.name = name
        # cached kernels are shared across queue worker threads; guard the
        # per-shape jit cache's check-then-insert
        self._jit_cache: Dict[tuple, Callable] = {}
        self._jit_lock = threading.Lock()

    # the per-shape jit cache holds live jax callables; drop it (and the
    # lock) when the compilation cache pickles us to the disk tier
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_jit_cache"] = {}
        state.pop("_jit_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._jit_lock = threading.Lock()

    def __call__(self, buffers: Dict[str, np.ndarray],
                 global_size: Sequence[int],
                 scalars: Optional[Dict[str, object]] = None,
                 jit: bool = True,
                 group_range: Optional[Sequence[int]] = None
                 ) -> Dict[str, jax.Array]:
        """Launch over ``global_size``.  ``group_range=(lo, hi)`` executes
        only that contiguous range of linearized work-groups of the full
        NDRange (the multi-device co-execution unit, runtime/scheduler.py);
        group-id decoding is unchanged, so results over the sub-range are
        identical to the same groups of a full launch.  The outputs are
        device arrays on the device that holds the input buffers
        (:meth:`repro.runtime.platform.Device.launch` places them)."""
        gsz = tuple(global_size)
        grange = None if group_range is None \
            else (int(group_range[0]), int(group_range[1]))
        scalars = scalars or {}
        if not (jit and self.prog.jittable):
            return self.prog.run_ndrange(buffers, scalars, gsz,
                                         group_range=grange)
        key = (gsz, grange, tuple(sorted((k, v.shape, str(v.dtype))
                                         for k, v in buffers.items())))
        with self._jit_lock:
            fn = self._jit_cache.get(key)
            if fn is None:
                def launch(bufs, scals):
                    return self.prog.run_ndrange(bufs, scals, gsz,
                                                 group_range=grange)
                fn = jax.jit(launch)
                self._jit_cache[key] = fn
        return fn(buffers, {k: np.asarray(v) for k, v in scalars.items()})

    # compiler introspection (used by tests/benchmarks)
    @property
    def num_regions(self) -> int:
        return len(self.prog.wg.regions)

    @property
    def context_stats(self) -> Dict[str, int]:
        return self.prog.plan.stats(self.prog.L)

    @property
    def work_group_plan(self) -> WorkGroupPlan:
        """The shared target-independent plan this kernel was built from."""
        return self.prog.wgplan

    @property
    def region_md(self) -> Dict[str, object]:
        """Per-region :class:`~repro.core.passes.ParallelRegionMD`."""
        return self.prog.md


def _run_pipeline(fn: Function, local_size: Sequence[int], target: str,
                  horizontal: bool, merge_uniform: bool,
                  use_vml: bool,
                  plan_cache: Optional[CompilationCache] = None,
                  _ir: Optional[str] = None) -> CompiledKernel:
    """One compilation = the (cacheable) target-independent prefix + the
    target-specific parallel mapping.  With a ``plan_cache``, the prefix —
    the pass-manager pipeline producing the :class:`WorkGroupPlan` — is
    looked up by :class:`PlanKey` and shared across targets and local
    sizes of the same kernel; only the thin mapping layer runs per
    target."""
    global _compiles_done
    with _compiles_lock:
        _compiles_done += 1
    name = fn.name
    if plan_cache is not None:
        pkey = PlanKey.make(_ir if _ir is not None else ir_hash(fn),
                            horizontal=horizontal,
                            merge_uniform=merge_uniform)
        plan = plan_cache.get_or_build_plan(
            pkey, lambda: build_plan(fn, horizontal=horizontal,
                                     merge_uniform=merge_uniform))
    else:
        plan = build_plan(fn, horizontal=horizontal,
                          merge_uniform=merge_uniform)
    if target == "vector":
        prog = WGProgram(plan, local_size, horizontal=horizontal,
                         merge_uniform=merge_uniform, use_vml=use_vml)
    elif target == "loop":
        prog = LoopWGProgram(plan, local_size, horizontal=horizontal,
                             merge_uniform=merge_uniform, use_vml=use_vml)
    elif target == "pallas":
        from .targets.pallas_target import PallasWGProgram
        prog = PallasWGProgram(plan, local_size, horizontal=horizontal,
                               merge_uniform=merge_uniform, use_vml=use_vml)
    else:
        raise InvalidArgError(f"unknown target {target!r}")
    return CompiledKernel(prog, name)


def _compile_kernel(build: Callable[[], Function],
                    local_size: Sequence[int],
                    target: str = "vector",
                    horizontal: bool = True,
                    merge_uniform: bool = True,
                    use_vml: bool = False,
                    cache: Union[bool, CompilationCache, None] = True,
                    device_key: Optional[str] = None,
                    plan_cache: Optional[CompilationCache] = None):
    """Compile ``build()`` for ``local_size`` on ``target``.

    ``cache=True`` uses the process-default compilation cache; pass a
    :class:`CompilationCache` for a private one (runtime devices do) or
    ``False``/``None`` to always recompile.  ``target="auto"`` defers the
    choice to the autotuner and returns an
    :class:`repro.core.autotune.AutotunedKernel`; ``device_key`` names the
    device the tuning decision belongs to (runtime devices pass their
    name), so heterogeneous devices tune independently.  Compiled code is
    device-independent here, so ``device_key`` never enters the
    compilation-cache key — only the tuning-table key.

    ``plan_cache`` holds the *stage-level* cache for the
    target-independent pipeline prefix (:class:`WorkGroupPlan`).  It
    defaults to the kernel cache, so a cold multi-target sweep of one
    kernel (the autotuner's) runs region formation exactly once; pass it
    explicitly to share plans across compiles that bypass the kernel
    cache (the autotuner does).  ``cache=False`` with no explicit
    ``plan_cache`` recompiles everything, plan included.
    """
    opts = dict(horizontal=horizontal, merge_uniform=merge_uniform,
                use_vml=use_vml)
    cache_obj: Optional[CompilationCache]
    if cache is True:
        cache_obj = default_cache()
    elif isinstance(cache, CompilationCache):
        cache_obj = cache
    else:
        cache_obj = None
    if plan_cache is None:
        plan_cache = cache_obj
    fn = build()
    if target == "auto":
        from .autotune import (AutotunedKernel, DEFAULT_CANDIDATES,
                               default_table)
        return AutotunedKernel(fn, build, local_size, opts,
                               DEFAULT_CANDIDATES, default_table(),
                               cache_obj, _compile_kernel,
                               device_key=device_key or "",
                               plan_cache=plan_cache)
    if cache_obj is None:
        return _run_pipeline(fn, local_size, target, plan_cache=plan_cache,
                             **opts)
    key = CacheKey.make(fn, local_size, target, **opts)
    return cache_obj.get_or_compile(
        key, lambda: _run_pipeline(fn, local_size, target,
                                   plan_cache=plan_cache, _ir=key.ir,
                                   **opts))


def compile_kernel(build: Callable[[], Function],
                   local_size: Sequence[int],
                   target: str = "vector",
                   **opts):
    """Deprecated host entry point — compile ``build()`` directly.

    Superseded by the first-class host object model (docs/host_api.md)::

        ctx = Context()
        prog = ctx.create_program(build)
        kernel = prog.create_kernel(name)

    which routes the identical compilation (same cache keys, same
    compile counts) through :class:`~repro.core.program.Program`'s lazy
    per-(device, local_size, target) specialization and adds typed
    argument validation.  This shim stays for existing call sites and
    benchmarks of the compiler layer; new code should build kernels
    through a :class:`~repro.runtime.context.Context`.
    """
    warnings.warn(
        "compile_kernel() is deprecated as a host entry point; build a "
        "Context and use ctx.create_program(build).create_kernel(name) "
        "(docs/host_api.md)", DeprecationWarning, stacklevel=2)
    return _compile_kernel(build, local_size, target=target, **opts)
