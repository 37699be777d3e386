"""Flash attention forward as a Pallas TPU kernel.

TPU adaptation notes (DESIGN.md §2): the grid is ``(batch*heads, q_blocks,
k_blocks)`` with the KV axis innermost — TPU grids execute sequentially, so
the running softmax state (row max ``m``, normalizer ``l``, accumulator)
lives in VMEM scratch that persists across the k-block steps of one q block.
Block shapes default to MXU-aligned 128×128 tiles; ``(block_q, head_dim)``
and ``(block_k, head_dim)`` tiles are the VMEM working set, so
``vmem_bytes ≈ (bq + 2*bk) * D * bytes + bq*D*4`` — block sizes are chosen to
keep this under ~4 MB while filling the 128×128 MXU.

GQA is handled in the BlockSpec index maps (query head h reads kv head
``h // (H // Hkv)``) — no materialized ``repeat_kv``.

Causal masking supports ``Sq != Sk`` (the query block is aligned to the tail
of the key sequence, as in incremental prefill).  With ``causal=True`` fully
masked k-blocks are *skipped* via ``pl.when`` — they still occupy grid steps
but issue no MXU work (the grid-pruning variant is a recorded §Perf item).
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               sm_scale: float, causal: bool, block_q: int, block_k: int,
               n_kb: int, sq: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_off = qi * block_q + (sk - sq)          # causal alignment offset
    k_off = ki * block_k

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_off + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
            cols = k_off + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip k blocks entirely above the diagonal of this q block
        block_needed = k_off <= q_off + block_q - 1
        pl.when(block_needed)(compute)
    else:
        compute()

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k",
                              "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    *, interpret: bool) -> jnp.ndarray:
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_qb, n_kb = Sq // block_q, Sk // block_k
    scale = float(sm_scale) if sm_scale is not None else 1.0 / float(np.sqrt(D))

    kernel = functools.partial(
        _fa_kernel, sm_scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kb=n_kb, sq=Sq, sk=Sk)

    return pl.pallas_call(
        kernel,
        grid=(B * H, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, qi, ki: (bh // H, (bh % H) // group,
                                             ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, qi, ki: (bh // H, (bh % H) // group,
                                             ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
