"""Sharding rules: UB-Mesh topology-aware logical-axis -> mesh-axis maps.

The production mesh is ("data", "model") = (16, 16) per pod, plus a leading
"pod" axis (2) for multi-pod.  Mapping follows the paper's hierarchy (§5.2):

* "model" = the intra-rack high-bandwidth 2D-FullMesh domain -> carries the
  TP/SP-class traffic: sequence-parallel activations, tensor-sharded weight
  dims, MoE expert dim, SSM head dim, KV-cache sequence dim.
* "data" (+ "pod") = the inter-rack mesh / HRS Clos tier -> carries the
  DP-class traffic: batch dim, ZeRO-1 optimizer shards, FSDP dims of the
  100B+ experts.

The DP width is read from the mesh (``mesh.shape``), so the same rules
serve the production mesh and a 2x2 mesh of one TPU host.

``ShardingRules.pspec`` drops an axis that is already used by an earlier
tensor dim, so ONE rule set adapts between train (sp-sharded activations =
FSDP-like weight gathers) and decode (sp off => ff/vocab dims take "model" =
classic TP).  See DESIGN.md §5.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import ParamSpec, ShardingRules, is_spec

MODEL_AXIS = "model"
DATA_AXIS = "data"
POD_AXIS = "pod"


def make_rules(
    *,
    multi_pod: bool = False,
    sp: bool = True,                 # sequence-parallel activations (train)
    batch_shardable: bool = True,    # False for global_batch=1 cells
    moe_strategy: str | None = None,
    extra: dict | None = None,
) -> ShardingRules:
    dp = (POD_AXIS, DATA_AXIS) if multi_pod else (DATA_AXIS,)
    rules: dict = {
        # activations
        "batch": dp if batch_shardable else None,
        "sp": MODEL_AXIS if sp else None,
        "ff_act": MODEL_AXIS,
        "cache_seq": MODEL_AXIS,
        "ssm_heads": MODEL_AXIS,
        # weights (all these dims divide 16 for every zoo arch)
        "qkv": MODEL_AXIS,
        "kv": MODEL_AXIS,
        "ff": MODEL_AXIS,
        "rkv": MODEL_AXIS,
        "ssm_proj": MODEL_AXIS,
        "ssm_inner": MODEL_AXIS,
        "table_embed": MODEL_AXIS,
        "vocab": MODEL_AXIS,
        "embed_in": None,
        "layers": None,
    }
    if moe_strategy == "expert_parallel":
        rules.update(
            experts=MODEL_AXIS,
            experts_act=MODEL_AXIS,
            moe_fsdp=DATA_AXIS,
            moe_ff_act=None,
            moe_d_act=MODEL_AXIS,
        )
    elif moe_strategy == "expert_tp":
        rules.update(
            experts=None,
            experts_act=None,
            moe_fsdp=DATA_AXIS,
            moe_ff_act=MODEL_AXIS,
            moe_d_act=MODEL_AXIS,
        )
    if extra:
        rules.update(extra)
    return ShardingRules(rules=rules)


def _dp_axes(mesh_shape) -> tuple[str, ...]:
    """The data-parallel axes present in a mesh, outermost first."""
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh_shape)


def dp_size(mesh_shape) -> int:
    """Data-parallel width of a mesh: the product of its DP axes."""
    return int(np.prod([mesh_shape[a] for a in _dp_axes(mesh_shape)]))


def rules_for_cell(harness, cell, mesh_shape) -> ShardingRules:
    """Pick the per-(arch x shape) rule set for a mesh (``mesh.shape``)."""
    dp = dp_size(mesh_shape)
    return make_rules(
        multi_pod=POD_AXIS in mesh_shape,
        sp=cell.kind != "decode",
        batch_shardable=cell.global_batch % dp == 0,
        moe_strategy=harness.moe_strategy,
    )


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state sharding over the DP axes
# ---------------------------------------------------------------------------


def zero1_pspec(spec: ParamSpec, rules: ShardingRules, mesh_shape) -> P:
    """Param pspec + the DP axes added on the first free, divisible dim.

    This is the ZeRO-1 partitioning of fp32 master/moment tensors: model-
    sharded dims stay, and one replicated dim additionally shards over the
    mesh's DP axes ("pod", "data").  Falls back to the plain param spec
    when nothing divides.
    """
    base = rules.pspec(spec.logical)
    entries = list(base) + [None] * (len(spec.shape) - len(base))
    used = {a for e in entries if e for a in ((e,) if isinstance(e, str) else e)}
    free = tuple(a for a in _dp_axes(mesh_shape) if a not in used)
    if not free:
        return base
    dp_total = int(np.prod([mesh_shape[a] for a in free]))
    # skip the scanned-layers dim (dim 0 when logical starts with "layers")
    start = 1 if spec.logical and spec.logical[0] == "layers" else 0
    for i in range(start, len(spec.shape)):
        if entries[i] is None and spec.shape[i] % dp_total == 0 and spec.shape[i] > 0:
            entries[i] = free if len(free) > 1 else free[0]
            break
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_zero1_pspecs(spec_tree, rules: ShardingRules, mesh_shape):
    return jax.tree.map(
        lambda s: zero1_pspec(s, rules, mesh_shape), spec_tree, is_leaf=is_spec
    )
