"""Topology-aware collectives — explicit shard_map lowering (paper §5.1).

GSPMD usually derives collective schedules from sharding annotations; these
functions make the paper's hierarchical schedules EXPLICIT where that
matters (gradient sync, MoE dispatch), so the compiled HLO provably follows
the Multi-Ring / hierarchical pattern:

* ``hierarchical_allreduce`` — reduce-scatter over the FAST axis (intra-rack
  2D-FM = "model"), all-reduce over the SLOW axes ("data", "pod"), then
  all-gather back over the fast axis.  Wire bytes on the slow (expensive)
  links drop by the fast-axis size — the Multi-Ring tiering of Fig. 13.
* ``hierarchical_all_to_all`` — the Fig. 14-(b) broadcast/reduce-style MoE
  dispatch: A2A within the local clique first, then one exchange across
  cliques (dedups the long-link copies).
* ``multipath_split`` — the Fig. 14-(a) trick at the JAX level: split a
  tensor in two and route the halves over two different mesh axes
  simultaneously (bandwidth of both dimensions adds).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def hierarchical_allreduce(mesh: Mesh, fast_axis: str, slow_axes: tuple[str, ...]):
    """Returns fn(x_sharded_anyhow) -> allreduced x, lowered hierarchically.

    x enters replicated per-device shard-wise (shard_map sees local shards);
    semantics match a flat psum over (fast, *slow) but the schedule is
    RS(fast) -> AR(slow) -> AG(fast).
    """

    def inner(x):
        n_fast = mesh.shape[fast_axis]
        # reduce-scatter over the fast axis: each fast-rank owns 1/n_fast
        x = jax.lax.psum_scatter(x, fast_axis, scatter_dimension=0, tiled=True)
        # all-reduce the owned shard over the slow (long-range) axes
        for ax in slow_axes:
            x = jax.lax.psum(x, ax)
        # gather the fast axis back
        x = jax.lax.all_gather(x, fast_axis, axis=0, tiled=True)
        return x

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=P(), out_specs=P(),
        check_vma=False,
    )


def flat_allreduce(mesh: Mesh, axes: tuple[str, ...]):
    """Baseline: single flat psum over all axes (for wire-byte comparison)."""

    def inner(x):
        return jax.lax.psum(x, axes)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)


def multipath_split(mesh: Mesh, axis_a: str, axis_b: str):
    """Fig. 14-(a): move a tensor across the mesh via TWO axes at once.

    Splits x in half; half 1 rides an all_gather over axis_a, half 2 over
    axis_b — on the physical 2D-FullMesh both dimension's links carry
    traffic simultaneously, doubling per-pair bandwidth.
    """

    def inner(x):
        h = x.shape[0] // 2
        a = jax.lax.all_gather(x[:h], axis_a, axis=0, tiled=True)
        b = jax.lax.all_gather(x[h:], axis_b, axis=0, tiled=True)
        return a, b

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
                         check_vma=False)


def hierarchical_all_to_all(mesh: Mesh, intra_axis: str, inter_axis: str):
    """Two-stage A2A: exchange within the local clique first, then one
    exchange across cliques (the Fig. 14-(b/c) hierarchy).

    x: (n_intra * n_inter, chunk, ...) — destination-major layout.
    """

    def inner(x):
        n_intra = mesh.shape[intra_axis]
        n_inter = mesh.shape[inter_axis]
        # stage 1: intra-clique exchange of the inter-destined groups
        x = x.reshape(n_inter, n_intra, *x.shape[1:])
        x = jax.lax.all_to_all(x, intra_axis, split_axis=1, concat_axis=1, tiled=False)
        # stage 2: one cross-clique exchange
        x = jax.lax.all_to_all(x, inter_axis, split_axis=0, concat_axis=0, tiled=False)
        return x.reshape(n_inter * n_intra, *x.shape[2:])

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)
