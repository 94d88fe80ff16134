"""Pipeline parallelism: GPipe-style microbatch pipeline via shard_map.

The paper maps PP onto the inter-rack axis (P2P boundary transfers, <0.2%
of traffic — Table 1).  This module implements the schedule as a
``shard_map`` over a "stage" mesh axis with ``jax.lax.ppermute`` boundary
transfers, so the compiled HLO carries exactly the paper's collective
pattern (collective-permute on the "data"/inter-rack axis).

Used for memory-constrained configs (the planner decides when); the
dry-run's default cells use DP×TP/SP which already fit, so PP is exercised
by its own unit test (tests/test_pipeline.py, 4 host devices) and available
via ``pipelined_forward`` for launchers.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(
    mesh: Mesh,
    stage_axis: str,
    stage_fn: Callable,     # (stage_params, x) -> x  per-stage computation
    n_microbatches: int,
):
    """Build a pipelined forward: params sharded over the stage axis
    (leading dim = n_stages), batch split into microbatches.

    GPipe schedule: T = n_micro + n_stages - 1 ticks; at each tick every
    stage processes its resident microbatch then ppermutes the activation
    to the next stage.  Returns fn(stage_params, x) -> y where x and y are
    (n_micro, mb, ...) batches; y is the last stage's output, broadcast to
    every stage by one psum (only the last stage's buffer is non-zero), so
    no caller has to slice the sharded stage dim.
    """
    n_stages = mesh.shape[stage_axis]

    def per_stage(params, x):
        # params: this stage's slice (leading dim 1); x: full microbatches
        # on every stage (only stage 0's content matters at tick 0)
        stage = jax.lax.axis_index(stage_axis)
        p = jax.tree.map(lambda t: t[0], params)
        n_ticks = n_microbatches + n_stages - 1

        def tick(carry, t):
            buf, outputs = carry
            # which microbatch is entering stage 0 at tick t
            mb_in = jnp.where(t < n_microbatches, t, 0)
            incoming = jnp.where(
                (stage == 0) & (t < n_microbatches),
                x[mb_in],
                buf,
            )
            y = stage_fn(p, incoming)
            # last stage collects its finished microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            collect = (stage == n_stages - 1) & (out_idx >= 0)
            outputs = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outputs,
            )
            # boundary transfer: stage i -> i+1 (paper's PP P2P)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, i + 1) for i in range(n_stages - 1)],
            )
            return (nxt, outputs), None

        buf0 = jnp.zeros_like(x[0])
        outs0 = jnp.zeros_like(x)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks)
        )
        return jax.lax.psum(outputs, stage_axis)   # (n_micro, mb, ...)

    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),        # params staged; x replicated
        out_specs=P(),                        # the last stage's y, everywhere
        check_vma=False,
    )


def stage_split(tree, n_stages: int):
    """Split a stacked-layer param tree (L, ...) into (n_stages, L/st, ...)."""
    def f(x):
        L = x.shape[0]
        assert L % n_stages == 0
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(f, tree)
