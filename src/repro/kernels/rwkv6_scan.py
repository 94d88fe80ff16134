"""RWKV6 chunked linear-attention scan — Pallas TPU kernel.

Grid (B*H, n_chunks), last axis sequential; per-head state (N, N fp32) in
VMEM scratch.  The intra-chunk part uses the numerically safe DIRECT
pairwise decay form (every exponent <= 0), tiled into (T x T) sub-blocks so
the (T, T, N) temporary stays in VMEM — the same tiling as the jnp
reference (models/rwkv6.rwkv6_chunked), here made explicit per-core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(
    r_ref,      # (1, Q, 1, N)
    k_ref,      # (1, Q, 1, N)
    v_ref,      # (1, Q, 1, N)
    l_ref,      # (1, Q, 1, N)   log decay (<= 0)
    u_ref,      # (1, N)         bonus
    y_ref,      # (1, Q, 1, N)
    sout_ref,   # (1, N, N)
    s_ref,      # scratch (N, N) fp32
    *,
    tile: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    rq = r_ref[0, :, 0, :].astype(jnp.float32)      # (Q, N)
    kq = k_ref[0, :, 0, :].astype(jnp.float32)
    vq = v_ref[0, :, 0, :].astype(jnp.float32)
    lq = l_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                # (N,)
    Q, N = rq.shape

    cum = jnp.cumsum(lq, axis=0)                    # (Q, N)
    # inter-chunk: y_i += (r_i * exp(cum_i - l_i)) S
    y = jax.lax.dot_general(
        rq * jnp.exp(cum - lq), s_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                               # (Q, N)
    # bonus diagonal term
    y = y + jnp.sum(rq * u[None, :] * kq, axis=1, keepdims=True) * vq

    # intra-chunk: tiled pairwise decay (exponents <= 0, always safe)
    n_tiles = Q // tile
    ci_dec = cum - lq
    for ti in range(n_tiles):
        i0 = ti * tile
        ri = rq[i0 : i0 + tile]
        di = ci_dec[i0 : i0 + tile]
        acc = jnp.zeros((tile, N), jnp.float32)
        for tj in range(ti + 1):
            j0 = tj * tile
            kj = kq[j0 : j0 + tile]
            vj = vq[j0 : j0 + tile]
            cj = cum[j0 : j0 + tile]
            d = di[:, None, :] - cj[None, :, :]     # (T, T, N)
            if ti == tj:
                mask = jnp.tril(jnp.ones((tile, tile), jnp.bool_), k=-1)
                d = jnp.where(mask[:, :, None], d, -jnp.inf)
            att = jnp.einsum("in,jn,ijn->ij", ri, kj, jnp.exp(d))
            acc = acc + jax.lax.dot_general(
                att, vj, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        y = jax.lax.dynamic_update_slice_in_dim(
            y, y[i0 : i0 + tile] + acc, i0, axis=0
        )

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: S' = diag(prod w) S + sum_j (prod_{t>j} w) k_j v_j^T
    tail = jnp.exp(cum[-1:, :] - cum)               # (Q, N)
    s_ref[...] = s_ref[...] * jnp.exp(cum[-1])[:, None] + jax.lax.dot_general(
        kq * tail, vq, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    sout_ref[0] = s_ref[...]


def rwkv6_scan(
    r: jax.Array,       # (B, S, H, N)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,       # decay in (0, 1)
    u: jax.Array,       # (H, N)
    *,
    chunk: int = 128,
    tile: int = 16,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    B, S, H, N = r.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    logw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-6, 1.0))

    kernel = functools.partial(_rwkv_kernel, tile=min(tile, Q))
    spec = pl.BlockSpec((1, Q, 1, N), lambda h, c: (h // H, c, h % H, 0))
    y, s = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            spec,
            spec,
            spec,
            spec,
            pl.BlockSpec((1, N), lambda h, c: (h % H, 0)),
        ],
        out_specs=[
            spec,
            pl.BlockSpec((1, N, N), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
    return y, s.reshape(B, H, N, N)
