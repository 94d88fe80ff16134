"""Jitted train/serve step builders with full sharding annotations.

These are the functions the launcher jits and the dry-run lowers.  The
in/out shardings come from the harness's ParamSpec logical axes + the
topology-aware rules (parallel/sharding.py); optimizer state uses the
ZeRO-1 pspecs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.api import Harness, ShapeCell
from repro.models.layers import Runtime
from repro.models.param import (
    ShardingRules,
    is_spec,
    tree_abstract,
    tree_pspecs,
)
from repro.optim import adamw
from repro.optim.compression import CompressionConfig, compress_grads
from repro.parallel.sharding import rules_for_cell, tree_zero1_pspecs


@dataclass
class StepBundle:
    """Everything needed to jit/lower one (arch x shape x mesh) cell."""

    fn: Callable
    in_shardings: Any
    out_shardings: Any
    abstract_args: tuple
    donate_argnums: tuple = ()


def _shardings(mesh: Mesh, pspec_tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_train_step(
    loss_fn: Callable,
    opt_cfg: adamw.OptConfig,
    compression: CompressionConfig,
) -> Callable:
    """``(params, opt_state, batch) -> (params, opt_state, metrics)``: one
    AdamW step on ``loss_fn``'s gradients.  The launcher jits it on one
    device; ``build_train_step`` jits it with mesh shardings."""

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("optimizer"):
            grads, _ = compress_grads(compression, grads)
            new_params, new_opt, metrics = adamw.apply(opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def build_train_step(
    harness: Harness,
    cell: ShapeCell,
    mesh: Mesh,
    *,
    opt_cfg: adamw.OptConfig | None = None,
    compression: CompressionConfig | None = None,
    rules: ShardingRules | None = None,
) -> StepBundle:
    opt_cfg = opt_cfg or adamw.OptConfig()
    compression = compression or CompressionConfig()
    rules = rules or rules_for_cell(harness, cell, mesh.shape)
    loss_fn = harness.loss(Runtime(rules=rules))

    param_specs = harness.param_specs()
    opt_specs = adamw.opt_state_specs(param_specs)
    input_specs = harness.train_input_specs(cell)

    param_ps = tree_pspecs(param_specs, rules)
    opt_ps = {
        "master": tree_zero1_pspecs(param_specs, rules, mesh.shape),
        "m": tree_zero1_pspecs(param_specs, rules, mesh.shape),
        "v": tree_zero1_pspecs(param_specs, rules, mesh.shape),
        "step": P(),
    }
    input_ps = tree_pspecs(input_specs, rules)

    abstract = (
        tree_abstract(param_specs, dtype=jnp.bfloat16),
        tree_abstract(opt_specs),
        tree_abstract(input_specs),
    )
    in_sh = (
        _shardings(mesh, param_ps),
        _shardings(mesh, opt_ps),
        _shardings(mesh, input_ps),
    )
    out_sh = (
        _shardings(mesh, param_ps),
        _shardings(mesh, opt_ps),
        None,
    )
    return StepBundle(
        fn=make_train_step(loss_fn, opt_cfg, compression),
        in_shardings=in_sh,
        out_shardings=out_sh,
        abstract_args=abstract,
        donate_argnums=(0, 1),
    )


def build_serve_step(
    harness: Harness,
    cell: ShapeCell,
    mesh: Mesh,
    *,
    rules: ShardingRules | None = None,
) -> StepBundle:
    """Prefill (cell.kind == 'prefill') or decode step bundle."""
    rules = rules or rules_for_cell(harness, cell, mesh.shape)
    rt = Runtime(rules=rules)

    param_specs = harness.param_specs()
    state_specs = harness.serve_state_specs(cell)
    input_specs = harness.serve_input_specs(cell)

    param_ps = tree_pspecs(param_specs, rules)
    state_ps = tree_pspecs(state_specs, rules)
    input_ps = tree_pspecs(input_specs, rules)

    if cell.kind == "prefill":
        inner = harness.prefill(rt)
    else:
        inner = harness.decode(rt)

    def serve_step(params, state, inputs):
        logits, new_state = inner(params, state, **inputs)
        return logits, new_state

    abstract = (
        tree_abstract(param_specs, dtype=jnp.bfloat16),
        tree_abstract(state_specs),
        tree_abstract(input_specs),
    )
    in_sh = (
        _shardings(mesh, param_ps),
        _shardings(mesh, state_ps),
        _shardings(mesh, input_ps),
    )
    out_sh = (None, _shardings(mesh, state_ps))
    return StepBundle(
        fn=serve_step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        abstract_args=abstract,
        donate_argnums=(1,),
    )


def build_bundle(harness, cell: ShapeCell, mesh, **kw) -> StepBundle:
    if cell.kind == "train":
        return build_train_step(harness, cell, mesh, **kw)
    return build_serve_step(harness, cell, mesh)


def lower_bundle(bundle: StepBundle, mesh: Mesh):
    """jit().lower() under the mesh — the dry-run entry point."""
    jitted = jax.jit(
        bundle.fn,
        in_shardings=bundle.in_shardings,
        out_shardings=bundle.out_shardings,
        donate_argnums=bundle.donate_argnums,
    )
    with mesh:
        return jitted.lower(*bundle.abstract_args)
