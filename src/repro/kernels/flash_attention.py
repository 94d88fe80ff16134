"""Flash attention (GQA) — Pallas TPU kernel.

Online-softmax blocked attention: the grid walks (batch*kv_head, q_block,
kv_block); running max/denominator/accumulator live in VMEM scratch and the
output block is written on the LAST kv step.  Block shapes are MXU-aligned
(multiples of 128 where the head_dim allows; q/kv block = 128 rows).

Supports causal masking, sliding windows and bidirectional prefixes — the
union of what the zoo needs (starcoder2/mixtral SWA, paligemma prefix-LM,
whisper bidirectional encoder via causal=False).

Layout: q (B, K, G, Sq, D)  k/v (B, K, Sk, D)  — G = query heads per kv
head folded into the q-block rows so one kernel serves MHA/GQA/MQA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _attn_kernel(
    q_ref,      # (1, 1, G, BQ, D)
    k_ref,      # (1, 1, BK, D)
    v_ref,      # (1, 1, BK, D)
    o_ref,      # (1, 1, G, BQ, D)
    m_ref,      # scratch (G, BQ)       running max
    l_ref,      # scratch (G, BQ)       running denom
    acc_ref,    # scratch (G, BQ, D)    running numerator
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    prefix_len: int,
    bq: int,
    bk: int,
    nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                  # (G, BQ, BK)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok = ok & (q_pos >= k_pos)
    if window is not None:
        ok = ok & ((q_pos - k_pos) < window)
    if prefix_len > 0:
        ok = ok | (k_pos < prefix_len)
    s = jnp.where(ok[None], s, NEG_INF)

    m_prev = m_ref[...]                           # (G, BQ)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])             # (G, BQ, BK)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-20)[..., None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # (B, K, G, Sq, D)
    k: jax.Array,            # (B, K, Sk, D)
    v: jax.Array,            # (B, K, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    prefix_len: int = 0,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool,
) -> jax.Array:
    B, K, G, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        prefix_len=prefix_len,
        bq=bq,
        bk=bk,
        nk=nk,
    )
    grid = (B * K, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, G, bq, D), lambda h, i, j: (h // K, h % K, 0, i, 0)
            ),
            pl.BlockSpec((1, 1, bk, D), lambda h, i, j: (h // K, h % K, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda h, i, j: (h // K, h % K, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, bq, D), lambda h, i, j: (h // K, h % K, 0, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
