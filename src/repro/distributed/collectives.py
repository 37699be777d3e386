"""Hierarchical cross-pod gradient reduction.

On a multi-pod mesh the data-parallel axis factors as (pod, data).  The
naive all-reduce moves every gradient byte across the (slow, few-link)
pod interconnect once per participant.  The hierarchical schedule
  1. reduce-scatter inside each pod      (fast ICI, 1/data of the bytes)
  2. all-reduce the scattered shards across pods (DCN, bytes/data)
  3. all-gather inside each pod          (fast ICI)
moves only 1/data of the gradient bytes over the pod axis.  Expressed as
a shard_map wrapper so it composes with the pjit step; XLA can find this
schedule itself in common cases, but pinning it makes the cross-pod
traffic explicit and predictable at 1000+ node scale.
"""

from __future__ import annotations


import jax
from jax.sharding import Mesh, PartitionSpec as P


def hierarchical_psum(tree, mesh: Mesh):
    """psum over ('pod','data') done hierarchically; call inside
    shard_map.  Falls back to a flat psum when there is no pod axis."""
    if "pod" not in mesh.axis_names:
        return jax.tree.map(lambda g: jax.lax.psum(g, "data"), tree)

    def one(g):
        # 1. reduce_scatter in-pod over 'data'
        scat = jax.lax.psum_scatter(g, "data", scatter_dimension=0,
                                    tiled=True)
        # 2. all-reduce across pods (small shards)
        scat = jax.lax.psum(scat, "pod")
        # 3. all-gather in-pod
        return jax.lax.all_gather(scat, "data", axis=0, tiled=True)

    return jax.tree.map(one, tree)


def hierarchical_grad_reduce(grad_fn, mesh: Mesh, batch_spec):
    """Wrap a per-shard grad function so its output grads are reduced
    hierarchically.  grad_fn(params, batch) -> grads (unreduced, local).
    Params replicated; batch sharded by batch_spec along ('pod','data')."""
    def inner(params, batch):
        grads = grad_fn(params, batch)
        return hierarchical_psum(grads, mesh)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(P(), batch_spec),
                         out_specs=P(),
                         check_vma=False)
