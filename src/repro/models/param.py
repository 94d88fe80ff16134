"""Parameter trees with logical sharding axes.

Every model in the zoo describes its parameters as a pytree of ``ParamSpec``
(shape + init + *logical axis names*).  From one spec tree we derive:

* materialized parameters        (``tree_init`` — smoke tests / examples),
* ``jax.ShapeDtypeStruct`` trees (``tree_abstract`` — the dry-run, NO alloc),
* ``PartitionSpec`` trees        (``tree_pspecs`` via a ``ShardingRules`` map).

Logical axes used across the zoo (all sharded dims are constructed to divide
the 16-way "model" axis evenly — virtual KV heads, padded vocabs, seq-CP):

    vocab      — padded vocabulary dim
    embed      — d_model residual dim
    heads      — query heads (sharded only in head-TP mode)
    kv_heads   — virtual KV heads (replicated up to a multiple of 16)
    head_dim   — per-head dim
    ff         — MLP hidden dim
    experts    — MoE expert dim
    layers     — scanned layer stack dim (never sharded)
    conv/state — SSM internals (never sharded)
    fsdp       — extra weight-sharding dim over the data axis (ZeRO-3 style)
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _init_leaf(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "scaled":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        return (
            jax.random.normal(key, spec.shape, spec.dtype)
            * (1.0 / math.sqrt(fan_in))
        )
    return jax.random.normal(key, spec.shape, spec.dtype) * spec.scale


def tree_init(spec_tree: PyTree, key: jax.Array, dtype=None) -> PyTree:
    """Materialize a spec tree.  One jitted program makes every leaf, so
    a leaf cast to ``dtype`` is never held in float32 on the device, and
    a full-width model costs one compile instead of several per leaf."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for spec, k in zip(leaves, keys):
            x = _init_leaf(spec, k)
            if dtype is not None:
                x = x.astype(dtype)
            out.append(x)
        return out

    return jax.tree.unflatten(treedef, make(key))


def tree_abstract(spec_tree: PyTree, dtype=None) -> PyTree:
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype),
        spec_tree,
        is_leaf=is_spec,
    )


@dataclass(frozen=True)
class ShardingRules:
    """Logical-axis -> mesh-axis mapping.

    ``rules`` maps a logical name to a mesh axis name (or tuple of axes, or
    None).  Unlisted logical names are unsharded.
    """

    rules: dict[str, Any]

    def pspec(self, logical: tuple[str | None, ...]) -> P:
        axes = []
        used: set[str] = set()
        for name in logical:
            ax = self.rules.get(name) if name else None
            if ax is None:
                axes.append(None)
                continue
            # one mesh axis may shard only one tensor dim
            flat = (ax,) if isinstance(ax, str) else tuple(ax)
            flat = tuple(a for a in flat if a not in used)
            if not flat:
                axes.append(None)
                continue
            used.update(flat)
            axes.append(flat[0] if len(flat) == 1 else flat)
        while axes and axes[-1] is None:
            axes.pop()
        return P(*axes)


def tree_pspecs(spec_tree: PyTree, rules: ShardingRules) -> PyTree:
    return jax.tree.map(lambda s: rules.pspec(s.logical), spec_tree, is_leaf=is_spec)


def tree_shardings(spec_tree: PyTree, rules: ShardingRules, mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, rules.pspec(s.logical)),
        spec_tree,
        is_leaf=is_spec,
    )


def param_count(spec_tree: PyTree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return sum(int(np.prod(s.shape)) for s in leaves)


def param_bytes(spec_tree: PyTree, bytes_per_elem: int = 2) -> int:
    return param_count(spec_tree) * bytes_per_elem


# ---------------------------------------------------------------------------
# helpers used by every model family
# ---------------------------------------------------------------------------


def stack_specs(spec_tree: PyTree, n_layers: int) -> PyTree:
    """Add a leading scanned-layers dim to every ParamSpec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec(
            (n_layers,) + s.shape,
            ("layers",) + s.logical,
            init=s.init,
            scale=s.scale,
            dtype=s.dtype,
        ),
        spec_tree,
        is_leaf=is_spec,
    )


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def cast_floats(tree: PyTree, dtype) -> PyTree:
    """Cast float leaves to the compute dtype (fp32 masters -> bf16)."""
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def virtual_kv_heads(n_kv: int, tp: int = 16) -> int:
    """Replicate KV heads so the kv-head dim divides the model axis."""
    if n_kv % tp == 0:
        return n_kv
    if tp % n_kv == 0:
        return tp
    return round_up(n_kv, tp)
