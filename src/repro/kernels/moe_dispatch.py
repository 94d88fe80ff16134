"""MoE capacity-bucketed dispatch matmul — Pallas TPU kernel.

Computes expert inputs ``out[e, c, :] = sum_t disp[t, e, c] * x[t, :]`` — the
GShard dispatch einsum — as a blocked matmul: grid (E, n_token_blocks) with
the token axis sequential, accumulating each expert's (C, D) buffer in VMEM.
The one-hot dispatch block arrives VMEM-resident and feeds the MXU directly
(one (C, BT) x (BT, D) matmul per step) — no gather/scatter engines needed,
which is exactly why this formulation is the TPU-native MoE.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(
    d_ref,      # (BT, 1, C)  dispatch block for this expert
    x_ref,      # (BT, D)
    o_ref,      # (1, C, D)
    acc_ref,    # scratch (C, D) fp32
    *,
    nt: int,
):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = d_ref[:, 0, :].astype(jnp.float32)          # (BT, C)
    x = x_ref[...].astype(jnp.float32)              # (BT, D)
    acc_ref[...] += jax.lax.dot_general(
        d, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (C, D)

    @pl.when(ti == nt - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_dispatch(
    disp: jax.Array,    # (T, E, C) one-hot dispatch
    x: jax.Array,       # (T, D)
    *,
    block_t: int = 128,
    interpret: bool,
) -> jax.Array:
    """Returns expert inputs (E, C, D)."""
    T, E, C = disp.shape
    D = x.shape[-1]
    bt = min(block_t, T)
    assert T % bt == 0
    nt = T // bt

    kernel = functools.partial(_dispatch_kernel, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(E, nt),
        in_specs=[
            pl.BlockSpec((bt, 1, C), lambda e, t: (t, e, 0)),
            pl.BlockSpec((bt, D), lambda e, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, D), lambda e, t: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((C, D), jnp.float32)],
        interpret=interpret,
    )(disp, x)


def moe_gather_matmul(
    disp: jax.Array,    # (T, E, C)
    x: jax.Array,       # (T, D)
    w: jax.Array,       # (E, D, F)
    *,
    interpret: bool,
) -> jax.Array:
    """Dispatch + expert matmul: (E, C, F)."""
    ein = moe_dispatch(disp, x, interpret=interpret)        # (E, C, D)
    return jnp.einsum("ecd,edf->ecf", ein.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)
