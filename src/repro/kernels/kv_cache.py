"""The decode step's two kernels over the stacked KV cache, as Pallas TPU
kernels.

The cache of a whole model is one array per K and V, ``(L, B, KV, D, S)``:
layers, batch rows, KV heads, head_dim, positions.  The position axis is
minor because that is the layout the TPU gives the cache anyway (a
``head_dim`` of 64 is narrower than the 128 lanes), so neither kernel
converts a layout, and neither copies a layer out of the stack:

* :func:`cache_write` writes each row's new token at that row's own
  length into layer ``layer``, in place (``input_output_aliases``).  It
  copies the ``(KV, D, 128)`` lane block that holds each row's position
  into VMEM, for K and V and for many rows at once, selects the one
  column with a ``where``, and copies the blocks back: 48 KB each way
  per row and tensor at smollm's widths, the least the cache's tiled
  layout lets a DMA move.
* :func:`decode_attention` attends one query token per row over layer
  ``layer``, read through the scalar-prefetched index in ``(KV, D,
  block_k)`` blocks with a running softmax: QK is ``q(g, D) @ K(D, bk)``
  and PV contracts ``bk`` on both operands.  It accumulates in float32.

Semantics (and the oracles) are :func:`repro.kernels.ref.cache_write`
and :func:`repro.kernels.ref.layer_decode_attention`.
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
#: the lane width: a write moves one lane block of this many positions
LANES = 128
#: a block dimension of one element that the kernel does not see
_SQ = pl.Squeezed()


def _write_kernel(layer_ref, pos_ref, kn_ref, vn_ref, kc_hbm, vc_hbm,
                  ko_hbm, vo_hbm, kbuf, vbuf, sem):
    """Rows ``[i * n, (i + 1) * n)`` of one grid step: read each row's lane
    block of K and V (all copies in flight at once), put the new token in
    its column, and write the blocks back."""
    del kc_hbm, vc_hbm                        # the same buffers as ko, vo
    n, width = kbuf.shape[0], kbuf.shape[-1]
    row0 = pl.program_id(0) * n
    layer = layer_ref[0]

    def block(hbm, r):
        lane0 = pl.multiple_of(pos_ref[row0 + r] // width * width, width)
        return hbm.at[layer, row0 + r, :, :, pl.ds(lane0, width)]

    def move(to_vmem):
        # start every row's copies, then wait for them all
        for wait in (False, True):
            @pl.loop(0, n)
            def _(r):
                for hbm, buf in ((ko_hbm, kbuf), (vo_hbm, vbuf)):
                    src, dst = block(hbm, r), buf.at[r]
                    if not to_vmem:
                        src, dst = dst, src
                    cp = pltpu.make_async_copy(src, dst, sem)
                    cp.wait() if wait else cp.start()

    move(True)
    lanes = jax.lax.broadcasted_iota(jnp.int32, kbuf.shape[1:], 2)
    for r in range(n):
        hit = lanes == pos_ref[row0 + r] % width
        # row r's new token is lane r of the step's (KV, D, n) block
        kbuf[r] = jnp.where(hit, kn_ref[:, :, r:r + 1], kbuf[r])
        vbuf[r] = jnp.where(hit, vn_ref[:, :, r:r + 1], vbuf[r])
    move(False)


def _rows_per_step(batch: int, block_bytes: int,
                  budget: int = 4 << 20) -> int:
    """The most rows, a divisor of ``batch``, whose K and V lane blocks
    fit ``budget`` bytes of VMEM together."""
    n = max(1, min(batch, budget // (2 * block_bytes)))
    while batch % n:
        n -= 1
    return n


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_write(k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                k: jnp.ndarray, v: jnp.ndarray, lengths: jnp.ndarray,
                layer, *, interpret: bool):
    """Write row ``b``'s ``k[b]``, ``v[b]`` (``(B, KV, D)``) at position
    ``min(lengths[b], S - 1)`` of layer ``layer`` of the caches
    (``(L, B, KV, D, S)``); every other element is left as it was.
    Returns the updated caches, which alias the inputs.  A row moves one
    lane block each way, or the whole position axis where S is not a
    multiple of 128."""
    L, B, KV, D, S = k_cache.shape
    dt = k_cache.dtype
    width = LANES if S % LANES == 0 else S
    n = _rows_per_step(B, KV * D * width * dt.itemsize)
    pos = jnp.minimum(jnp.broadcast_to(lengths, (B,)), S - 1) \
        .astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def steps(new):
        return new.astype(dt).reshape(B // n, n, KV, D).transpose(0, 2, 3, 1)

    # the new tokens as (B // n, KV, D, n): one step's rows lie along the
    # lanes of one block, a few KB, where a (B, KV, D, 1) array would pad
    # every element to a lane row of its own
    new_spec = pl.BlockSpec((_SQ, KV, D, n), lambda i, lr, pr: (i, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B // n,),
        in_specs=[new_spec, new_spec, hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((n, KV, D, width), dt),
                        pltpu.VMEM((n, KV, D, width), dt),
                        pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_cache.shape, dt),
                   jax.ShapeDtypeStruct(v_cache.shape, dt)),
        # operands: layer, pos, k, v, k_cache, v_cache
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(layer, pos, steps(k), steps(v), k_cache, v_cache)


def _attn_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, sm_scale: float, block_k: int,
                 n_kb: int):
    del layer_ref                             # used by the index maps only
    ki = pl.program_id(1)
    kv_heads, group = q_ref.shape[0], q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]

    @pl.when(ki * block_k < length)
    def _compute():
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1)
        for h in range(kv_heads):
            k = k_ref[h]                                      # (D, bk)
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(cols < length, s, NEG_INF)          # (g, bk)
            m_prev = m_scr[h]                                 # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[h]                                      # (D, bk)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (g, D)
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _block_positions(kv_heads: int, head_dim: int, seq: int,
                    itemsize: int, budget: int = 1 << 20) -> int:
    """The largest multiple of 128 positions that divides ``seq`` and
    keeps one ``(KV, D, block)`` block of K within ``budget`` bytes (K
    and V, double-buffered, then take 4x that); all of ``seq`` where it
    is not a multiple of 128."""
    if seq % LANES:
        return seq
    per_pos = kv_heads * head_dim * itemsize
    block = min(seq, max(LANES, budget // per_pos // LANES * LANES))
    while seq % block:
        block -= LANES
    return block


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_k",
                                             "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray, layer,
                     sm_scale: Optional[float] = None,
                     block_k: Optional[int] = None, *,
                     interpret: bool) -> jnp.ndarray:
    """q: (B, H, D); caches: (L, B, KV, D, S); lengths: (B,) valid
    positions of each row; ``layer``: the layer to read -> (B, H, D)."""
    B, H, D = q.shape
    L, _, KV, _, S = k_cache.shape
    group = H // KV
    if block_k is None:
        block_k = _block_positions(KV, D, S, k_cache.dtype.itemsize)
    assert S % block_k == 0, (S, block_k)
    n_kb = S // block_k
    scale = float(sm_scale) if sm_scale is not None \
        else 1.0 / float(np.sqrt(D))
    kernel = functools.partial(_attn_kernel, sm_scale=scale,
                               block_k=block_k, n_kb=n_kb)
    qo_spec = pl.BlockSpec((_SQ, KV, group, D),
                           lambda b, ki, lr, lens: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((_SQ, _SQ, KV, D, block_k),
                           lambda b, ki, lr, lens: (lr[0], b, 0, 0, ki))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kb),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, group, 1), jnp.float32),
            pltpu.VMEM((KV, group, 1), jnp.float32),
            pltpu.VMEM((KV, group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.broadcast_to(lengths, (B,)).astype(jnp.int32),
      q.reshape(B, KV, group, D), k_cache, v_cache)
    return out.reshape(B, H, D)
