"""CCU in-line reduce — Pallas TPU kernel (paper §7's co-processor analogue).

The paper's Collective Communication Unit reads peer buffers and reduces
them IN-LINE into on-chip SRAM, skipping the copy through the application's
HBM buffer and keeping a deterministic reduction order.  The TPU analogue:
a blocked kernel whose grid walks (chunk, peer) with the peer axis
sequential — the fp32 accumulator for the current chunk never leaves VMEM,
peers are streamed in deterministic order p=0..P-1, and one optional
bf16/int8 dequant happens on the fly (compressed-gradient ingestion).

On real hardware the peer dimension is fed by ICI remote DMA; here the
peers arrive as a stacked array so the kernel semantics (tiling, ordering,
accumulation dtype) are exactly testable in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ccu_kernel(
    in_ref,     # (1, BN) one peer's chunk
    scale_ref,  # (1, 1) dequant scale for this peer
    o_ref,      # (BN,)
    acc_ref,    # scratch (BN,) fp32
    *,
    np_: int,
):
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = in_ref[0].astype(jnp.float32) * scale_ref[0, 0]
    acc_ref[...] += x

    @pl.when(pi == np_ - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def ccu_reduce(
    bufs: jax.Array,             # (P, N) peer buffers (any float/int8 dtype)
    scales: jax.Array | None = None,   # (P,) dequant scales (int8 ingestion)
    *,
    block_n: int = 512,
    interpret: bool,
) -> jax.Array:
    """Deterministic-order peer reduction -> (N,) fp32."""
    P, N = bufs.shape
    bn = min(block_n, N)
    assert N % bn == 0
    if scales is None:
        scales = jnp.ones((P,), jnp.float32)
    scales2 = scales.reshape(P, 1).astype(jnp.float32)

    kernel = functools.partial(_ccu_kernel, np_=P)
    return pl.pallas_call(
        kernel,
        grid=(N // bn, P),
        in_specs=[
            pl.BlockSpec((1, bn), lambda n, p: (p, n)),
            pl.BlockSpec((1, 1), lambda n, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda n, p: (n,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn,), jnp.float32)],
        interpret=interpret,
    )(bufs, scales2)
