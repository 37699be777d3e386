"""Dispatch wrappers: Pallas kernel vs pure-jnp reference.

The model stack calls these; ``use_pallas`` selects the hand-written Pallas
kernels, which run in interpret mode only where the default backend is the
CPU (:func:`repro.backend.pallas_interpret`) and compile through Mosaic on
the TPU.  The reference path is the default for training
(XLA-differentiable) and for the multi-pod dry-run.  This mirrors pocl
linking device-optimized built-in libraries at IR level: same call site,
target-specific implementation.

The decode step's two operations on the stacked KV cache
(:func:`cache_write`, :func:`layer_decode_attention`) take no
``use_pallas``: their kernels run wherever the program is lowered for a
TPU and the cache's position axis is not sharded, and the oracles run
everywhere else (the CPU, and a dry-run that shards the positions).
"""

from __future__ import annotations

import functools

import jax

from repro.backend import pallas_interpret

from . import kv_cache as _kv
from . import ref
from .decode_attention import decode_attention as _dec_pallas
from .flash_attention import flash_attention as _fa_pallas
from .rmsnorm import rmsnorm as _rms_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


def attention(q, k, v, causal: bool = True, use_pallas: bool = False,
              block_q: int = 128, block_k: int = 128):
    if use_pallas:
        return _fa_pallas(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=pallas_interpret())
    return ref.attention(q, k, v, causal=causal)


def decode_attention(q, k_cache, v_cache, lengths, use_pallas: bool = False,
                     block_k: int = 256):
    if use_pallas:
        return _dec_pallas(q, k_cache, v_cache, lengths, block_k=block_k,
                           interpret=pallas_interpret())
    return ref.decode_attention(q, k_cache, v_cache, lengths)


def _tpu_kernel_or_ref(kernel, reference, positions_sharded: bool, *args):
    """``kernel(*args)`` where the program is lowered for a TPU and the
    positions are not sharded (a Pallas call is not partitioned);
    ``reference(*args)`` otherwise.  The platform is chosen when the
    program is lowered, so a compile for a described chip takes the
    kernel too."""
    if positions_sharded:
        return reference(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=reference)


def cache_write(k_cache, v_cache, k, v, lengths, layer, *,
                positions_sharded: bool):
    """Each row's new token into layer ``layer`` of the stacked caches
    (L, B, KV, D, S), at the row's own length, in place."""
    return _tpu_kernel_or_ref(
        functools.partial(_kv.cache_write, interpret=False),
        ref.cache_write, positions_sharded,
        k_cache, v_cache, k, v, lengths, layer)


def layer_decode_attention(q, k_cache, v_cache, lengths, layer, *,
                           positions_sharded: bool):
    """One query token per row over layer ``layer`` of the stacked caches
    (L, B, KV, D, S), read where it lies."""
    return _tpu_kernel_or_ref(
        functools.partial(_kv.decode_attention, interpret=False),
        ref.layer_decode_attention, positions_sharded,
        q, k_cache, v_cache, lengths, layer)


def rmsnorm(x, w, eps: float = 1e-6, use_pallas: bool = False):
    if use_pallas:
        return _rms_pallas(x, w, eps=eps, interpret=pallas_interpret())
    return ref.rmsnorm(x, w, eps=eps)


def ssd_scan(x, dt, A, B, C, chunk: int = 64, use_pallas: bool = False):
    if use_pallas:
        return _ssd_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=pallas_interpret())
    return ref.ssd_scan(x, dt, A, B, C, chunk=chunk, return_state=True)
