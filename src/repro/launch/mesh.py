"""Mesh construction (harness-specified shapes).

The constructors are FUNCTIONS so importing this module never touches jax
device state.  Axes map onto the UB-Mesh hierarchy:
"model" = intra-rack 2D-FullMesh (high-bandwidth TP/SP domain),
"data"  = inter-rack 2D-FullMesh, "pod" = HRS Clos tier.

Every axis is ``Auto``: the models place activations with
``with_sharding_constraint`` on logical axes (``Runtime.shard``) and let
GSPMD choose the collectives, which ``Explicit`` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto-typed."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1, *, devices=None):
    """Small ("data", "model") mesh: CPU tests (that many host devices) or
    the chips of one host."""
    return make_mesh((data, model), ("data", "model"), devices=devices)
