"""OpenCL-shaped runtime: host layer over the device layer (paper §3).

Layering (docs/runtime.md, docs/memory.md, docs/host_api.md):

  context.py    — Context: the host object-model root (shared caches,
                  pooled allocation, programs/kernels/queues)
  events.py     — Event / UserEvent: status ladder + profiling counters
  queue.py      — CommandQueue: the event-DAG scheduler per device
  scheduler.py  — CoExecutor: one NDRange split across several devices
  platform.py   — Platform / Device / Buffer (clGetPlatformIDs et al.)
  bufalloc.py   — the pocl buffer allocator + span-granular residency
  memory.py     — sub-buffers, zero-copy map/unmap, size-class pooling
  trace.py      — ChromeTrace: event-DAG export for chrome://tracing;
                  span(): program spans for the profiler and the export
"""

from ..core.errors import (BuildError, InvalidArgError, InvalidBufferError,
                           ReproError, status_name)
from ..core.program import Kernel, Program
from .bufalloc import Bufalloc, OutOfMemory, ResidencyTracker
from .context import Context, default_context
from .events import (CommandError, DependencyError, Event, EventStatus,
                     UserEvent, chunk_counters, wait_for_events)
from .memory import (MAP_READ, MAP_READ_WRITE, MAP_WRITE,
                     MAP_WRITE_INVALIDATE, BufferPool, MapError,
                     MappedRegion, SubBuffer, create_sub_buffer)
from .platform import (Buffer, Device, DeviceInfo, Platform,
                       ThrottledDevice, create_buffer, default_platform)
from .queue import CommandQueue
from .scheduler import (AdaptiveSplitter, CoExecStats, CoExecutor,
                        SharedBuffer, ThroughputModel, device_class,
                        split_groups)
from .trace import ChromeTrace, validate_trace

__all__ = [
    "Context", "default_context", "Program", "Kernel",
    "ReproError", "InvalidArgError", "InvalidBufferError", "BuildError",
    "status_name",
    "Bufalloc", "OutOfMemory", "ResidencyTracker",
    "Event", "EventStatus", "UserEvent", "CommandError", "DependencyError",
    "wait_for_events", "chunk_counters",
    "Platform", "Device", "DeviceInfo", "ThrottledDevice", "Buffer",
    "create_buffer", "default_platform",
    "CommandQueue",
    "CoExecutor", "CoExecStats", "SharedBuffer", "split_groups",
    "ThroughputModel", "AdaptiveSplitter", "device_class",
    "MapError", "MappedRegion", "SubBuffer", "create_sub_buffer",
    "BufferPool", "MAP_READ", "MAP_WRITE", "MAP_READ_WRITE",
    "MAP_WRITE_INVALIDATE",
    "ChromeTrace", "validate_trace",
]
