"""Dispatch wrappers: Pallas kernel vs pure-jnp reference.

The model stack calls these; ``use_pallas`` selects the hand-written Pallas
kernels, which run in interpret mode only where the default backend is the
CPU (:func:`repro.backend.pallas_interpret`) and compile through Mosaic on
the TPU.  The reference path is the default for training
(XLA-differentiable) and for the multi-pod dry-run.  This mirrors pocl
linking device-optimized built-in libraries at IR level: same call site,
target-specific implementation.
"""

from __future__ import annotations

from repro.backend import pallas_interpret

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


def rmsnorm(x, w, eps: float = 1e-6, use_pallas: bool = False):
    if use_pallas:
        return _rms_pallas(x, w, eps=eps, interpret=pallas_interpret())
    return ref.rmsnorm(x, w, eps=eps)


def ssd_scan(x, dt, A, B, C, chunk: int = 64, use_pallas: bool = False):
    if use_pallas:
        return _ssd_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=pallas_interpret())
    return ref.ssd_scan(x, dt, A, B, C, chunk=chunk, return_state=True)
