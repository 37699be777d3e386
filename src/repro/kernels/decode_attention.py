"""Single-token decode attention over a KV cache, as a Pallas TPU kernel.

Decode attention is memory-bound (one query row against S cached keys), so
the kernel is organized to stream K/V blocks through VMEM exactly once:
grid ``(batch*kv_heads, k_blocks)``, running-softmax scratch like flash
attention, and a ``lengths`` scalar-prefetch operand masks the invalid cache
tail.  The query heads sharing one KV head (GQA) form one ``(group, D)``
block, so each K/V block is read once per KV head, and the last two block
dims equal the array's (Mosaic's tiling rule holds for any head count).
Block size tunes the VMEM footprint: ``2 * block_k * D * bytes``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rows(row_fn, n: int):
    """Stack ``row_fn(r)`` for the ``n`` query rows of a group.  Each
    row is its own one-row product, so a row's result does not depend on
    how many heads share its KV head."""
    rows = [row_fn(r) for r in range(n)]
    return rows[0] if n == 1 else jnp.concatenate(rows, axis=0)


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, block_k: int, n_kb: int, hkv: int):
    bg = pl.program_id(0)
    ki = pl.program_id(1)
    b = bg // hkv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, d)
        s = _rows(lambda r: jax.lax.dot_general(
            q[r:r + 1], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), q.shape[0])
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)              # (group, bk)
        m_prev = m_scr[...]                                   # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = _rows(lambda r: jax.lax.dot_general(
            p[r:r + 1], v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), p.shape[0])
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_k",
                                             "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     sm_scale: Optional[float] = None, block_k: int = 256,
                     *, interpret: bool) -> jnp.ndarray:
    """q: (B, H, D); caches: (B, Hkv, S, D); lengths: (B,) -> (B, H, D)."""
    B, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = H // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_kb = S // block_k
    scale = float(sm_scale) if sm_scale is not None \
        else 1.0 / float(np.sqrt(D))

    kernel = functools.partial(_dec_kernel, sm_scale=scale, block_k=block_k,
                               n_kb=n_kb, hkv=Hkv)
    qo_spec = pl.BlockSpec((1, 1, group, D),
                           lambda bg, ki, lens: (bg // Hkv, bg % Hkv, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda bg, ki, lens: (bg // Hkv, bg % Hkv, ki, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, n_kb),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(B, Hkv, group, D), k_cache,
      v_cache)
    return out.reshape(B, H, D)
