"""OpenCL-shaped host layer: Platform / Device / Buffer (paper §3, Fig. 2).

The host layer is generic; device-specific behaviour lives behind the
device-layer interface, mirroring pocl's ``basic`` / ``pthread`` / ``ttasim``
driver split:

  ``basic``   — single JAX device, serial work-group execution (loop target)
  ``vector``  — single JAX device, vectorized work-groups (vector target)
  ``pallas``  — Pallas grid execution (interpret on CPU, Mosaic on TPU)
  ``mesh``    — work-groups distributed over a jax.Mesh axis (the
                multi-device analogue of the pthread driver's TLP)
  ``auto``    — target picked per kernel shape by the autotuner

Device queries (global memory size, max work-group size, …) are delegated to
the device layer exactly as the paper describes for ``clGetDeviceInfo``.
Every device owns a :class:`repro.core.cache.CompilationCache`, so repeated
``build_kernel`` calls for the same kernel/local-size are hash lookups;
``Device.cache_stats()`` / ``Platform.cache_stats()`` surface hit/miss/tune
counters (the clGetDeviceInfo-style introspection for the cache subsystem).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from ..core.api import CompiledKernel, _compile_kernel
from ..core.cache import CompilationCache
from ..core.errors import InvalidBufferError
from ..core.ir import Function
from .bufalloc import Bufalloc, Chunk


@dataclasses.dataclass
class DeviceInfo:
    name: str
    driver: str                 # basic | vector | pallas | mesh
    global_mem_size: int
    local_mem_size: int
    max_work_group_size: int
    compute_units: int
    # CL_DEVICE_MEM_BASE_ADDR_ALIGN, in *bytes* (OpenCL reports bits):
    # sub-buffer origins must be multiples of this (docs/memory.md)
    mem_base_addr_align: int = 4


class Device:
    """Device-layer object (cl_device_id analogue).

    Owns resource management for its memory (a :class:`Bufalloc` arena),
    a private compilation cache, and the target its driver kind maps to
    (``basic``→loop, ``vector``→vector, ``pallas``→pallas, ``auto``→
    autotuned).  Command queues bind to exactly one device; multi-device
    work uses one queue per device (runtime/scheduler.py)."""

    def __init__(self, info: DeviceInfo, jax_device=None):
        self.info = info
        # the JAX device every launch of this device runs on
        self.jax_device = jax_device or jax.devices()[0]
        # Bufalloc manages the device buffer address space (the paper's
        # "host keeps book of all buffer allocations for a known region")
        self.allocator = Bufalloc(info.global_mem_size, greedy=True)
        self._target = {"basic": "loop", "vector": "vector",
                        "pallas": "pallas", "mesh": "vector",
                        "auto": "auto"}[info.driver]
        # per-device compilation cache (pocl: "the kernel compiler caches
        # the work-group function per kernel + local size"); the disk tier
        # activates when REPRO_KERNEL_CACHE_DIR is set
        self.compile_cache = CompilationCache.from_env()

    # -- device layer: kernel compilation -------------------------------------
    def compile(self, build: Callable[[], Function],
                local_size: Sequence[int], **opts) -> CompiledKernel:
        """Device-layer compilation: run the pocl pipeline for
        ``local_size`` on the device's target, memoized in the device
        cache.  Autotuned devices key their tuning decisions by device
        name, so co-executing heterogeneous devices measure
        independently.  This is the internal specialization primitive
        :meth:`repro.core.program.Program` builds on; host code should go
        through ``Context.create_program`` (docs/host_api.md)."""
        opts.setdefault("cache", self.compile_cache)
        opts.setdefault("device_key", self.info.name)
        opts.setdefault("target", self._target)
        return _compile_kernel(build, local_size, **opts)

    def build_kernel(self, build: Callable[[], Function],
                     local_size: Sequence[int], **opts) -> CompiledKernel:
        """Deprecated host entry point (clBuildProgram + clCreateKernel in
        one call).  Use ``Context.create_program(build)`` and specialize
        through :class:`~repro.core.program.Kernel` objects instead; this
        shim delegates to the same device-cache compilation."""
        warnings.warn(
            "Device.build_kernel() is deprecated; use Context."
            "create_program(build).create_kernel(name) and enqueue the "
            "Kernel object (docs/host_api.md)",
            DeprecationWarning, stacklevel=2)
        return self.compile(build, local_size, **opts)

    def launch(self, binary, buffers, global_size, scalars=None,
               group_range=None):
        """Run a compiled kernel (:meth:`compile`) with its buffers
        placed on :attr:`jax_device`; returns the outputs, which stay
        on that device until the caller reads them."""
        bufs = jax.device_put(dict(buffers), self.jax_device)
        return binary(bufs, global_size, scalars, group_range=group_range)

    def cache_stats(self) -> Dict[str, int]:
        """Compilation-cache counters for this device (hits, misses,
        compiles, evictions, disk traffic, tune decisions)."""
        return self.compile_cache.stats.as_dict()

    def query(self, what: str):
        return getattr(self.info, what)


class ThrottledDevice(Device):
    """A device that models a slower — or intermittently busy — member
    of a lopsided platform (the benchmark and test double for N-device
    asymmetric co-execution, docs/runtime.md §Scheduler).

    Kernels compiled on a ThrottledDevice run the *real* computation
    (results stay bitwise-identical to any other device) and then charge
    simulated time: ``seconds_per_group`` for every work-group in the
    executed range, plus any one-shot delay armed with :meth:`stall`
    (another tenant briefly hogging the device).  The charged time lands
    inside the chunk command, so it shows up in the event profiling
    counters exactly like real execution time — which is what the
    co-execution throughput model measures.

    With ``window_chunks=True`` (the default) a ``group_range``
    sub-launch is executed by running the *full-range* kernel through
    the normal cached jit trace and windowing out the chunk's linearized
    element span — so timing-dependent adaptive chunk boundaries never
    force a fresh ``(lo, hi)`` jit trace (~100ms each, which would drown
    the simulated per-group cost).  The windowing is exact for kernels
    where work-group ``g`` writes exactly its own linearized element
    span — elementwise kernels, which is what the lopsided benchmark
    runs.  For kernels with scattered cross-group writes pass
    ``window_chunks=False`` to delegate ``group_range`` untouched.

    ``coexec_class`` (default ``"<driver>-throttled"``) is the
    device-class key the scheduler persists split weights under — give
    fast and slow wrappers different classes so their learned weights
    never alias.  ``sleep`` is injectable so tests can run simulated
    platforms in virtual time.
    """

    def __init__(self, info: DeviceInfo, jax_device=None,
                 seconds_per_group: float = 0.0,
                 coexec_class: Optional[str] = None,
                 sleep: Optional[Callable[[float], None]] = None,
                 window_chunks: bool = True):
        super().__init__(info, jax_device)
        self.seconds_per_group = float(seconds_per_group)
        self.coexec_class = coexec_class or f"{info.driver}-throttled"
        self._sleep = sleep if sleep is not None else time.sleep
        self.window_chunks = bool(window_chunks)
        self._stall_s = 0.0
        self._stall_lock = threading.Lock()

    def stall(self, seconds: float) -> None:
        """Arm a one-shot delay charged to the next kernel execution on
        this device."""
        with self._stall_lock:
            self._stall_s += float(seconds)

    def _consume_stall(self) -> float:
        with self._stall_lock:
            s, self._stall_s = self._stall_s, 0.0
            return s

    def compile(self, build: Callable[[], Function],
                local_size: Sequence[int], **opts) -> "_ThrottledKernel":
        inner = super().compile(build, local_size, **opts)
        return _ThrottledKernel(inner, self,
                                tuple(int(x) for x in local_size))


class _ThrottledKernel:
    """Launchable proxy that charges its ThrottledDevice's simulated
    time per executed work-group (plus any armed stall) after running
    the real kernel."""

    def __init__(self, kernel, device: ThrottledDevice,
                 local_size: Sequence[int]):
        self._kernel = kernel
        self._device = device
        self._local = tuple(local_size)

    def __getattr__(self, name):
        return getattr(self._kernel, name)

    def _window(self, buffers, global_size, scalars, jit, lo, hi):
        """Execute groups ``[lo, hi)`` by windowing the cached full-range
        launch: bitwise-identical to a real ``group_range`` sub-launch
        for kernels whose group ``g`` writes its own linearized element
        span, and free of per-span retracing."""
        full = self._kernel(buffers, global_size, scalars, jit=jit)
        L = 1
        for x in self._local:
            L *= max(1, int(x))
        out = {}
        for nm, arr in buffers.items():
            base = np.asarray(arr)
            res = base.reshape(-1).copy()
            f = np.asarray(full[nm]).reshape(-1)
            res[lo * L:hi * L] = f[lo * L:hi * L]
            out[nm] = res.reshape(base.shape)
        return out

    def __call__(self, buffers, global_size, scalars=None, jit: bool = True,
                 group_range=None):
        d = self._device
        if group_range is not None:
            lo, hi = int(group_range[0]), int(group_range[1])
            groups = max(0, hi - lo)
            if d.window_chunks:
                out = self._window(buffers, global_size, scalars, jit,
                                   lo, hi)
            else:
                out = self._kernel(buffers, global_size, scalars, jit=jit,
                                   group_range=group_range)
        else:
            out = self._kernel(buffers, global_size, scalars, jit=jit)
            gsz = tuple(global_size) + (1,) * (3 - len(global_size))
            lsz = self._local + (1,) * (3 - len(self._local))
            groups = 1
            for g, l in zip(gsz, lsz):
                groups *= max(1, g // max(1, l))
        delay = d._consume_stall() + groups * d.seconds_per_group
        if delay > 0:
            d._sleep(delay)
        return out


class Buffer:
    """A device buffer (cl_mem analogue) backed by a Bufalloc chunk plus a
    host-side array mirror (the actual payload on this simulated device).

    The hierarchical-memory subsystem (:mod:`repro.runtime.memory`,
    docs/memory.md) extends every buffer with

    * **view bookkeeping** — :attr:`origin`/:attr:`root` let sub-buffer
      views and the root share one identity for residency and mapping;
    * **residency binding** — :meth:`bind_residency` attaches a
      :class:`~repro.runtime.bufalloc.ResidencyTracker`, after which any
      write through the buffer *or any aliased view of it* invalidates
      the overlapping span of every other device's copy;
    * **map bookkeeping** — active :class:`~repro.runtime.memory.
      MappedRegion`\\ s are registered on the root so overlapping write
      maps (and kernel launches over write-mapped buffers) are rejected.
    """

    def __init__(self, device: Device, size_bytes: int, dtype: str,
                 n_elems: int, pool=None, lazy: bool = False):
        self.device = device
        # a pool-backed buffer draws its chunk from (and releases it to)
        # a size-class BufferPool over the device arena instead of the
        # raw first-fit allocator (Context.create_buffer does this)
        self._pool = pool
        self._size_bytes = size_bytes
        # a lazy buffer defers both the chunk and the payload until first
        # real use, so a fusion-elided intermediate that is only ever the
        # stitched-away link of a chain never allocates at all
        # (docs/memory.md §Lazy pooled buffers)
        self.chunk: Optional[Chunk] = None if lazy else (
            pool.alloc(size_bytes) if pool is not None
            else device.allocator.alloc(size_bytes))
        self.dtype = dtype
        self.itemsize = np.dtype(dtype).itemsize
        self.n_elems = n_elems
        self.nbytes = n_elems * self.itemsize
        self.origin = 0                       # byte offset within root
        self._data: Optional[np.ndarray] = (None if lazy
                                            else np.zeros(n_elems, dtype))
        # residency binding (None until bind_residency)
        self._tracker = None
        self._res_key = None
        self._res_dev = None
        # zero-copy map bookkeeping (root buffers only)
        self._maps: List[object] = []         # active MappedRegions
        self._map_lock = threading.Lock()
        # optional read-back hook run by READ maps before publishing the
        # view (e.g. pull the canonical copy of a shared buffer);
        # MAP_WRITE_INVALIDATE skips it — that is the skipped read-back
        self.on_map_sync: Optional[Callable[[int, int], None]] = None

    @property
    def root(self) -> "Buffer":
        """The underlying root allocation (self for non-view buffers)."""
        return self

    # -- lazy materialization ---------------------------------------------------
    @property
    def materialized(self) -> bool:
        """True once the device chunk and payload exist.  Lazy buffers
        (``Context.create_buffer(pooled=True)``) stay unmaterialized
        until the first real use; an elided fusion intermediate is
        *never* real use, so its ``bytes_elided`` are genuinely saved."""
        return self._data is not None

    def _materialize(self) -> None:
        if self._data is not None:
            return
        if self.chunk is None:
            self.chunk = (self._pool.alloc(self._size_bytes)
                          if self._pool is not None
                          else self.device.allocator.alloc(self._size_bytes))
        self._data = np.zeros(self.n_elems, self.dtype)

    @property
    def data(self) -> np.ndarray:
        """The host-side payload mirror; touching it is 'first real use'
        and materializes a lazy buffer."""
        self._materialize()
        return self._data

    @data.setter
    def data(self, arr: np.ndarray) -> None:
        if self.chunk is None:
            self.chunk = (self._pool.alloc(self._size_bytes)
                          if self._pool is not None
                          else self.device.allocator.alloc(self._size_bytes))
        self._data = arr

    # -- residency ------------------------------------------------------------
    def bind_residency(self, tracker, key, device_key) -> None:
        """Attach a ResidencyTracker: from now on every write through
        this buffer or any of its views calls ``tracker.wrote_span`` for
        exactly the written byte span, invalidating other device copies
        at sub-buffer granularity."""
        self._tracker = tracker
        self._res_key = key
        self._res_dev = device_key

    def mark_written_span(self, lo: int, hi: int) -> None:
        """Record that bytes ``[lo, hi)`` (buffer-relative) were written
        on this buffer's device."""
        if self._tracker is not None:
            self._tracker.wrote_span(self._res_key, self._res_dev,
                                     self.origin + lo, self.origin + hi)

    def mark_written(self) -> None:
        self.mark_written_span(0, self.nbytes)

    # -- map bookkeeping (queried by CommandQueue._launch) ----------------------
    @property
    def map_count(self) -> int:
        """Number of active mapped regions over the *root* allocation."""
        with self.root._map_lock:
            return len(self.root._maps)

    def release(self) -> None:
        if self.chunk is not None:
            if self._pool is not None:
                self._pool.free(self.chunk)
            else:
                self.device.allocator.free(self.chunk)
            self.chunk = None


def global_mem_size(jax_device) -> int:
    """CL_DEVICE_GLOBAL_MEM_SIZE of a JAX device: the allocator limit
    the backend reports (``memory_stats()["bytes_limit"]``), or 1 GiB
    where it reports none (the CPU)."""
    stats = jax_device.memory_stats() or {}
    return int(stats.get("bytes_limit", 1 << 30))


class Platform:
    """clGetPlatformIDs analogue: enumerates devices for the process."""

    def __init__(self):
        self.devices: List[Device] = []
        jdevs = jax.devices()
        for i, d in enumerate(jdevs):
            self.devices.append(self._device(
                f"repro-{d.platform}-{i}", "vector", d,
                compute_units=len(jdevs)))
        # a 'basic' serial device is always available (pocl's reference)
        self.devices.append(self._device("repro-basic", "basic", jdevs[0]))
        self.devices.append(self._device("repro-pallas", "pallas",
                                         jdevs[0]))
        # an autotuned device: the target is picked per kernel shape by
        # measurement (the per-platform mapping choice of Rupp & Weinbub)
        self.devices.append(self._device("repro-auto", "auto", jdevs[0]))

    @staticmethod
    def _device(name: str, driver: str, jax_device,
                compute_units: int = 1) -> Device:
        return Device(DeviceInfo(
            name=name, driver=driver,
            global_mem_size=global_mem_size(jax_device),
            local_mem_size=1 << 20, max_work_group_size=1024,
            compute_units=compute_units), jax_device)

    def get_devices(self, driver: Optional[str] = None) -> List[Device]:
        """clGetDeviceIDs: all devices, or those of one driver kind."""
        if driver is None:
            return list(self.devices)
        return [d for d in self.devices if d.info.driver == driver]

    def co_devices(self, n: int, driver: str = "vector") -> List[Device]:
        """Create ``n`` fresh homogeneous devices for multi-device
        co-execution (the analogue of EngineCL's device set over one
        platform).  Each device owns its own allocator and compilation
        cache; the multi-device scheduler (runtime/scheduler.py) fans
        sub-ranges of one NDRange out across them.  Device ``i`` binds
        JAX device ``i % len(jax.devices())``: distinct chips while there
        are enough, shared ones after.  The devices are appended to
        :attr:`devices` so ``cache_stats`` sees them."""
        jdevs = jax.devices()
        out = [self._device(f"repro-co-{driver}-{i}", driver,
                            jdevs[i % len(jdevs)]) for i in range(n)]
        self.devices.extend(out)
        return out

    def cache_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-device compilation-cache counters, keyed by device name."""
        return {d.info.name: d.cache_stats() for d in self.devices}


def validate_buffer_request(n_elems, dtype) -> int:
    """Validate a buffer-creation request; returns the element size.

    Raises :class:`~repro.core.errors.InvalidBufferError`
    (CL_INVALID_BUFFER_SIZE) for a zero/negative/non-integral element
    count or an unknown dtype string — *before* the request reaches the
    Bufalloc arena, which would otherwise fail deep inside chunk
    bookkeeping with an untyped error (or silently clamp a zero-byte
    allocation to the alignment granule)."""
    if isinstance(n_elems, bool) or not isinstance(
            n_elems, (int, np.integer)):
        raise InvalidBufferError(
            f"buffer element count must be an integer, got "
            f"{type(n_elems).__name__} ({n_elems!r})")
    if n_elems <= 0:
        raise InvalidBufferError(
            f"buffer element count must be positive, got {n_elems}")
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError as e:
        raise InvalidBufferError(
            f"unknown buffer dtype {dtype!r}: {e}") from None
    return itemsize


def create_buffer(device: Device, n_elems: int, dtype: str = "float32",
                  pool=None, lazy: bool = False) -> Buffer:
    """clCreateBuffer: allocate ``n_elems`` of ``dtype`` on ``device``.
    ``pool`` (a :class:`~repro.runtime.memory.BufferPool` over the
    device's arena) serves the chunk from a size-class free list —
    ``Context.create_buffer`` passes the context's per-device pool.
    ``lazy=True`` defers chunk + payload to first real use (pooled
    context buffers default to this, enabling fusion elision)."""
    itemsize = validate_buffer_request(n_elems, dtype)
    return Buffer(device, int(n_elems) * itemsize, dtype, int(n_elems),
                  pool=pool, lazy=lazy)


# ---------------------------------------------------------------------------
# Process-default platform (lazy singleton)
# ---------------------------------------------------------------------------

_default_platform: Optional[Platform] = None
_platform_lock = threading.Lock()


def default_platform() -> Platform:
    """The process-default :class:`Platform` (clGetPlatformIDs returns the
    same platform object for every caller).  Subsystems that need *a*
    device for host-side command scheduling — e.g. the serving engine's
    DAG queue — share this one instead of enumerating devices per
    instance."""
    global _default_platform
    with _platform_lock:
        if _default_platform is None:
            _default_platform = Platform()
        return _default_platform
