"""Collective helpers for the distributed runtime.

Under pjit/GSPMD most collectives are implicit (inserted by the partitioner
from sharding constraints), so these helpers serve three purposes:

* explicit ``shard_map`` regions (pipeline parallelism, compressed
  reductions) that need hand-written collectives,
* hierarchical cross-pod gradient reduction (reduce within pod first, then
  across pods over DCI — less DCI traffic than a flat all-reduce when the
  per-pod mesh is large),
* reduce-scatter-based reductions that keep gradient shards distributed
  (ZeRO-2 style) instead of materializing full gradients per device.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axis checker off — the one
    shard_map entry point of the package.  Pallas calls inside a region
    have no replication rule, and the boundary specs come from the
    partition planner, re-derived by the ``dist.*`` verifier invariants."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def psum_hierarchical(x: jnp.ndarray, *, pod_axis: str = "pod",
                      data_axis: str = "data") -> jnp.ndarray:
    """All-reduce over (pod, data) as two stages: intra-pod first (fast ICI),
    then inter-pod (DCI).  Inside shard_map only."""
    x = jax.lax.psum(x, data_axis)
    return jax.lax.psum(x, pod_axis)


def reduce_scatter_mean(x: jnp.ndarray, axis_name: str,
                        split_dim: int = 0) -> jnp.ndarray:
    """Mean-reduce-scatter along ``split_dim`` (ZeRO-2 gradient shards)."""
    n = jax.lax.psum(1, axis_name)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=split_dim,
                                tiled=True) / n


def all_gather_params(tree: Any, axis_name: str, split_dim: int = 0) -> Any:
    """Gather FSDP-sharded leaves back to full size inside shard_map."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=split_dim,
                                     tiled=True), tree)


def tree_psum(tree: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jax.lax.psum(x, axis_name), tree)
