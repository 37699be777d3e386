"""Pallas target: work-groups on the TPU grid, lanes on the VPU.

The TPU-native parallel mapping (DESIGN.md §2): one work-group per grid
cell of a ``pl.pallas_call``; the work-item lane axis of the vector executor
becomes the 128-wide vector lane axis; OpenCL ``local`` memory becomes VMEM
scratch (materialized as register arrays here — locals are work-group
private, so they never leave the grid cell).  Barrier semantics need no
hardware primitive — after region formation the regions run in sequence over
full lane vectors (the same argument the paper makes for WI loops).

Global buffers are passed whole because generic SPMD kernels compute
arbitrary addresses; the TPU grid is sequential, so aliased output refs give
every work-group a consistent running view — legal under OpenCL's
no-inter-group-dependency contract.

The kernel runs in interpret mode only on the CPU
(:func:`repro.backend.pallas_interpret`).  On the TPU it compiles through
Mosaic, and a kernel Mosaic refuses raises the typed
:class:`~repro.core.errors.BuildError` with the compiler's message in its
build log; it never falls back to interpret mode.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...backend import pallas_interpret
from .. import ir
from ..errors import BuildError
from .vector import WGProgram


class PallasWGProgram(WGProgram):
    # scalar args must embed as jaxpr literals (pallas_call rejects
    # captured device constants), so the launch is not wrapped in jit:
    # run_ndrange compiles the pallas_call itself
    jittable = False

    @property
    def interpret(self) -> bool:
        """Interpret mode: on only when the default backend is the CPU."""
        return pallas_interpret()

    def run_ndrange(self, buffers: Dict[str, np.ndarray],
                    scalars: Optional[Dict[str, object]],
                    global_size: Sequence[int],
                    group_range: Optional[Tuple[int, int]] = None):
        """Execute the NDRange on the Pallas grid.  ``group_range=(lo,
        hi)`` shrinks the grid to ``hi - lo`` cells and offsets
        ``program_id`` by ``lo``, so the sub-range sees its true group ids
        of the full NDRange (multi-device co-execution unit)."""
        gsz = tuple(global_size) + (1,) * (3 - len(global_size))
        for g, l in zip(gsz, self.lsz):
            assert g % l == 0, "global size must divide local size"
        self.ngrp = tuple(g // l for g, l in zip(gsz, self.lsz))
        n_groups = int(np.prod(self.ngrp))
        lo, hi = (0, n_groups) if group_range is None \
            else (int(group_range[0]), int(group_range[1]))
        assert 0 <= lo <= hi <= n_groups, \
            f"group_range {group_range} outside [0, {n_groups}]"
        if hi == lo:
            return {k: jnp.asarray(v) for k, v in buffers.items()}
        self.scalars = {}
        scalars = scalars or {}
        for a in self.wg.fn.scalar_args:
            # numpy (not jnp) so the value embeds as a literal in the
            # kernel jaxpr — pallas_call rejects captured device consts
            self.scalars[a.name] = np.asarray(scalars[a.name],
                                              np.dtype(a.dtype))

        local_defs = [a for a in self.wg.fn.buffer_args
                      if a.space == ir.LOCAL and a.name not in buffers]
        bufs = {k: jnp.asarray(v) for k, v in buffers.items()}
        names = sorted(bufs)

        def kernel(*refs):
            # inputs are aliased to outputs: out_refs carry the running state
            out_refs = refs[len(names):]
            g = pl.program_id(0) + lo  # true group id within the full grid
            b = {nm: oref[...] for nm, oref in zip(names, out_refs)}
            for la in local_defs:
                b[la.name] = jnp.zeros((la.size,), la.dtype)
            out = self.run_wg(b, g)
            for nm, oref in zip(names, out_refs):
                oref[...] = out[nm]

        call = pl.pallas_call(
            kernel,
            grid=(hi - lo,),
            in_specs=[pl.BlockSpec(bufs[n].shape,
                                   lambda g, nd=bufs[n].ndim: (0,) * nd)
                      for n in names],
            out_specs=[pl.BlockSpec(bufs[n].shape,
                                    lambda g, nd=bufs[n].ndim: (0,) * nd)
                       for n in names],
            out_shape=[jax.ShapeDtypeStruct(bufs[n].shape, bufs[n].dtype)
                       for n in names],
            input_output_aliases={i: i for i in range(len(names))},
            interpret=self.interpret,
        )
        args = [bufs[n] for n in names]
        try:
            compiled = jax.jit(call).lower(*args).compile()
        except Exception as e:
            # the backend compiler (Mosaic on the TPU) refused the kernel
            name = self.wg.fn.name
            raise BuildError(
                f"pallas target: the backend compiler refused kernel "
                f"{name!r} ({type(e).__name__})",
                build_log=f"{type(e).__name__}: {e}") from e
        return dict(zip(names, compiled(*args)))
