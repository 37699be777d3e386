"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD insight (Dao & Gu, 2024) maps the selective-SSM recurrence onto
matmuls: within a chunk of length L the output is a masked (semiseparable)
attention-like product — MXU work — while the recurrent state only crosses
chunk boundaries.  TPU adaptation: grid ``(B, H, n_chunks)`` with the chunk
axis innermost; the inter-chunk state ``(N, P)`` lives in VMEM scratch and
persists across sequential grid steps, so the recurrence costs no HBM
traffic.  VMEM working set per step:
``L*P + 2*L*N + L + L*L + N*P`` floats — with L=64..256 this tiles well
under the ~16 MB VMEM budget while the (L,L) and (L,P) products fill the MXU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (L,)
    A = a_ref[0].astype(jnp.float32)                 # ()
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)

    dA = dt * A                                      # (L,) negative
    cs = jnp.cumsum(dA)                              # (L,)

    # intra-chunk (semiseparable "attention"):
    seg = cs[:, None] - cs[None, :]                  # (L, L)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lm = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    w = cb * Lm * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)

    # inter-chunk: contribution of the state entering this chunk
    state = state_scr[...]                            # (N, P)
    cstate = jax.lax.dot_general(Cm, state, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = y + jnp.exp(cs)[:, None] * cstate

    # state update: S' = exp(cs_L) S + B^T diag(dt * exp(cs_L - cs)) x
    decay_in = dt * jnp.exp(cs[-1] - cs)              # (L,)
    bx = jax.lax.dot_general(Bm, decay_in[:, None] * x,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (N, P)
    state_scr[...] = jnp.exp(cs[-1]) * state + bx

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0, :, :] = state_scr[...].T.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int = 64,
             *, interpret: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shapes as in :func:`repro.kernels.ref.ssd_scan`.

    Returns (y, final_state) with y: (b, s, h, p), state: (b, h, p, n).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, r=rep: (bi, ci, hi // r, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, r=rep: (bi, ci, hi // r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y, st
