"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (B, n_chunks) with the LAST axis sequential: the inter-chunk state
(H, P, N fp32) lives in VMEM scratch and persists across chunk steps.
Per chunk: intra-chunk decay attention (two MXU matmuls over (Q, Q)) plus
the state contribution — the exact math of models/mamba2.ssd_chunked,
tiled so the working set (chunk x heads x P + state) stays in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,      # (1, Q, H, P)
    l_ref,      # (1, Q, H)
    b_ref,      # (1, Q, N)
    c_ref,      # (1, Q, N)
    y_ref,      # (1, Q, H, P)
    hout_ref,   # (1, H, P, N)
    h_ref,      # scratch (H, P, N) fp32
    *,
    nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xq = x_ref[0].astype(jnp.float32)       # (Q, H, P)
    lq = l_ref[0].astype(jnp.float32)       # (Q, H)
    bq = b_ref[0].astype(jnp.float32)       # (Q, N)
    cq = c_ref[0].astype(jnp.float32)       # (Q, N)
    Q = xq.shape[0]

    cum = jnp.cumsum(lq, axis=0)            # (Q, H)
    scores = jax.lax.dot_general(
        cq, bq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                       # (Q, Q) = C_i . B_j
    decay = cum[:, None, :] - cum[None, :, :]          # (Q, Q, H)
    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    att = scores[:, :, None] * jnp.exp(
        jnp.where(causal[:, :, None], decay, -jnp.inf)
    )                                       # (Q, Q, H)
    # y_intra[i,h,p] = sum_j att[i,j,h] x[j,h,p]
    y_intra = jnp.einsum("ijh,jhp->ihp", att, xq)
    # inter-chunk from carried state
    y_inter = jnp.einsum("in,hpn->ihp", cq, h_ref[...]) * jnp.exp(cum)[:, :, None]
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    tail = jnp.exp(cum[-1:, :] - cum)                  # (Q, H)
    dh = jnp.einsum("jhp,jn,jh->hpn", xq, bq, tail)
    h_ref[...] = h_ref[...] * jnp.exp(cum[-1])[:, None, None] + dh
    hout_ref[0] = h_ref[...]


def ssd_scan(
    xh: jax.Array,      # (B, S, H, P)
    log_l: jax.Array,   # (B, S, H)
    Bm: jax.Array,      # (B, S, N)
    Cm: jax.Array,      # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    kernel = functools.partial(_ssd_kernel, nc=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, Q, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, Q, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), xh.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(xh, log_l, Bm, Cm)
    return y, h
