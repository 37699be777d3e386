"""Fused RMSNorm as a Pallas kernel, using Vecmathlib's rsqrt.

A deliberately simple kernel demonstrating the vml-inside-Pallas integration
(paper §5: built-ins linked into the kernel at IR level so they vectorize
with surrounding code): the normalizer uses :func:`repro.vml.rsqrt`
(Newton iteration on the magic-constant initial guess), which lowers to
straight VPU vector ops inside the kernel body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import vml


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, use_vml: bool):
    x = x_ref[...].astype(jnp.float32)          # (block_rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    r = vml.rsqrt(var + eps) if use_vml else jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * r * w[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "use_vml",
                                             "interpret"))
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
            block_rows: int = 128, use_vml: bool = True,
            *, interpret: bool) -> jnp.ndarray:
    """x: (..., d); w: (d,).  Rows are tiled over the grid."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, use_vml=use_vml)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out.reshape(orig_shape)
