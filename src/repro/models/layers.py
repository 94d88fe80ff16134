"""Shared layers for the architecture zoo.

All layers are pure functions ``(rt, params, x, ...) -> y`` where ``rt`` is a
:class:`Runtime` carrying the sharding rules (no-op when absent, so the same
code runs single-device smoke tests and 512-chip dry-runs).

Sharding strategy (see DESIGN.md §5): weights store their projection dims
FLATTENED — ``(d_model, n_heads*head_dim)`` etc. — because every such dim in
the zoo divides the 16-way "model" axis evenly, while head counts (24, 36, 8)
often don't.  Activations are sequence-sharded over "model" (the paper's SP /
ring-attention form; logical axis ``sp``), batch over "data"/"pod" (DP).  KV
caches shard their sequence dim (flash-decode style).  GSPMD inserts the
all-gathers/psums these annotations imply — that compiled collective schedule
is what the roofline reads.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .param import ParamSpec, ShardingRules


@dataclass(frozen=True)
class Runtime:
    """Sharding context threaded through every layer."""

    rules: ShardingRules | None = None
    use_kernels: bool = False         # route hot-spots through Pallas ops

    def shard(self, x: jax.Array, *logical: str | None) -> jax.Array:
        if self.rules is None:
            return x
        spec = self.rules.pspec(tuple(logical))
        return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int) -> ParamSpec:
    return ParamSpec((dim,), (None,), init="ones")


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w.astype(dt)


def layernorm_specs(dim: int) -> dict:
    return {
        "scale": ParamSpec((dim,), (None,), init="ones"),
        "bias": ParamSpec((dim,), (None,), init="zeros"),
    }


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y.astype(dt) * p["scale"].astype(dt)) + p["bias"].astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs    # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]                         # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    return jnp.concatenate(
        [
            (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin).astype(dt),
            (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin).astype(dt),
        ],
        axis=-1,
    )


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    window: int | None = None      # sliding-window size (None = full)
    rope_theta: float | None = 10000.0
    qkv_bias: bool = False
    prefix_len: int = 0            # bidirectional prefix (VLM / audio stubs)
    impl: str = "reference"        # reference | blocked (flash-style)


def attn_specs(cfg: AttnConfig) -> dict:
    """Flattened projections — every sharded dim divides the model axis."""
    D, N, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((D, N * Dh), ("embed_in", "qkv"), init="scaled"),
        "wk": ParamSpec((D, K * Dh), ("embed_in", "kv"), init="scaled"),
        "wv": ParamSpec((D, K * Dh), ("embed_in", "kv"), init="scaled"),
        "wo": ParamSpec((N * Dh, D), ("qkv", "embed_in"), init="scaled"),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((N * Dh,), ("qkv",), init="zeros")
        specs["bk"] = ParamSpec((K * Dh,), ("kv",), init="zeros")
        specs["bv"] = ParamSpec((K * Dh,), ("kv",), init="zeros")
        specs["bo"] = ParamSpec((D,), (None,), init="zeros")
    return specs


def _mask_bias(
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool,
    window: int | None,
    prefix_len: int = 0,
) -> jax.Array:
    """Additive attention bias (0 / -1e9), shape (Sq, Sk), float32.

    ``prefix_len`` makes the first N key positions visible to everyone
    (prefix-LM attention for VLM stubs, paligemma-style).
    """
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok = ok & (q_pos[:, None] >= k_pos[None, :])
    if window is not None:
        ok = ok & ((q_pos[:, None] - k_pos[None, :]) < window)
    if prefix_len > 0:
        ok = ok | (k_pos[None, :] < prefix_len)
    return jnp.where(ok, 0.0, -1e9).astype(jnp.float32)


def sdpa(
    q: jax.Array,      # (B, Sq, K, G, Dh)  q heads grouped by kv head
    k: jax.Array,      # (B, Sk, K, Dh)
    v: jax.Array,      # (B, Sk, K, Dh)
    bias: jax.Array | None,   # (Sq, Sk)
) -> jax.Array:
    """Reference grouped-query attention (the Pallas kernel's oracle)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias[None, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def blocked_sdpa(
    q: jax.Array,      # (B, Sq, K, G, Dh)
    k: jax.Array,      # (B, Sk, K, Dh)
    v: jax.Array,      # (B, Sk, K, Dh)
    *,
    q_start: int = 0,  # static global position of q[0] / k[0]
    k_start: int = 0,
    causal: bool,
    window: int | None,
    prefix_len: int,
    block_q: int = 2048,
    block_k: int = 2048,
) -> jax.Array:
    """Flash-style online-softmax attention with STATIC block skipping.

    The beyond-paper §Perf optimization (hypothesis H-mem in
    EXPERIMENTS.md): never materializes the (Sq, Sk) score matrix, and
    skips (q-block, kv-block) pairs that the causal/sliding-window mask
    rules out entirely — for starcoder2's 4K window at 32K prefill that's
    ~7/8 of all blocks.  Pure jnp (python loop = unrolled HLO), mirroring
    kernels/flash_attention.py which is the TPU execution path.
    """
    B, Sq, K, G, Dh = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    scale = 1.0 / math.sqrt(Dh)
    q0, k0 = q_start, k_start

    out_blocks = []
    for iq in range(nq):
        qs, qe = iq * bq, min((iq + 1) * bq, Sq)
        q_blk = q[:, qs:qe].astype(jnp.float32)
        q_lo, q_hi = q0 + qs, q0 + qe - 1
        m = jnp.full((B, qe - qs, K, G), -1e30, jnp.float32)
        l = jnp.zeros((B, qe - qs, K, G), jnp.float32)
        acc = jnp.zeros((B, qe - qs, K, G, Dh), jnp.float32)
        for ik in range(nk):
            ks_, ke = ik * bk, min((ik + 1) * bk, Sk)
            k_lo, k_hi = k0 + ks_, k0 + ke - 1
            # ---- static skip tests (whole block masked out?) -------------
            in_prefix = prefix_len > 0 and k_lo < prefix_len
            if not in_prefix:
                if causal and k_lo > q_hi:
                    continue
                if window is not None and (q_lo - k_hi) >= window:
                    continue
            k_blk = k[:, ks_:ke].astype(jnp.float32)
            v_blk = v[:, ks_:ke].astype(jnp.float32)
            s = jnp.einsum("bqkgd,bskd->bqkgs", q_blk, k_blk) * scale
            bias = _mask_bias(
                q0 + qs + jnp.arange(qe - qs),
                k0 + ks_ + jnp.arange(ke - ks_),
                causal, window, prefix_len,
            )
            s = s + bias[None, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqkgs,bskd->bqkgd", p, v_blk
            )
            m = m_new
        out_blocks.append(acc / jnp.maximum(l, 1e-20)[..., None])
    return jnp.concatenate(out_blocks, axis=1).astype(q.dtype)


def attention(
    rt: Runtime,
    p: dict,
    x: jax.Array,                  # (B, S, D)
    cfg: AttnConfig,
    positions: jax.Array,          # (S,) token positions for q
    kv_cache: tuple[jax.Array, jax.Array] | None = None,  # (B,Smax,K,Dh) x2
    cache_pos: jax.Array | None = None,  # scalar write offset (decode)
    kv_override: jax.Array | None = None,  # encoder states for cross-attn
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """Full attention layer.  Returns (out, updated_cache)."""
    B, S, D = x.shape
    N, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = N // K

    kv_src = kv_override if kv_override is not None else x
    q = jnp.einsum("bsd,dp->bsp", x, p["wq"])
    k = jnp.einsum("bsd,dp->bsp", kv_src, p["wk"])
    v = jnp.einsum("bsd,dp->bsp", kv_src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, N, Dh)
    k = k.reshape(B, kv_src.shape[1], K, Dh)
    v = v.reshape(B, kv_src.shape[1], K, Dh)
    q = rt.shard(q, "batch", "sp", None, None)

    if cfg.rope_theta is not None and kv_override is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        if cache_pos is not None:
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, cache_pos, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, cache_pos, 0, 0)
            )
        k, v = ck, cv
        new_cache = (ck, cv)
        k_pos = jnp.arange(k.shape[1])
        k = rt.shard(k, "batch", "cache_seq", None, None)
        v = rt.shard(v, "batch", "cache_seq", None, None)
    else:
        k_pos = positions
        # ring-attention allgather form: kv replicated across the sp shards
        k = rt.shard(k, "batch", None, None, None)
        v = rt.shard(v, "batch", None, None, None)

    qg = q.reshape(B, S, K, G, Dh)
    # blocked path: train (no cache) and full-length prefill (cache written
    # from position 0 over its whole extent => causal mask covers validity)
    blocked_ok = (
        cfg.impl == "blocked"
        and kv_override is None
        and (kv_cache is None or (S > 1 and S == k.shape[1]))
    )
    if blocked_ok:
        out = blocked_sdpa(
            qg, k, v,
            causal=cfg.causal, window=cfg.window, prefix_len=cfg.prefix_len,
        )
    else:
        if kv_override is not None:
            bias = None                                # cross-attn: full view
        else:
            # positions are the q tokens' GLOBAL positions, so the same mask
            # covers train (full S), prefill (cache write at 0) and decode
            # (single token at cache_pos)
            bias = _mask_bias(
                positions, k_pos, cfg.causal, cfg.window, cfg.prefix_len
            )
        out = sdpa(qg, k, v, bias)
    out = out.reshape(B, S, N * Dh)
    out = rt.shard(out, "batch", "sp", None)
    y = jnp.einsum("bsp,pd->bsd", out, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    y = rt.shard(y, "batch", "sp", None)
    return y, new_cache


def init_kv_cache(
    cfg: AttnConfig, batch: int, max_len: int, n_layers: int, dtype=jnp.bfloat16
) -> dict:
    """Stacked (L, B, S, K, Dh) cache specs for the scanned decoder."""
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    logical = ("layers", "batch", "cache_seq", None, None)
    return {
        "k": ParamSpec(shape, logical, init="zeros", dtype=dtype),
        "v": ParamSpec(shape, logical, init="zeros", dtype=dtype),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_specs(d_model: int, d_ff: int) -> dict:
    return {
        "w_gate": ParamSpec((d_model, d_ff), ("embed_in", "ff"), init="scaled"),
        "w_up": ParamSpec((d_model, d_ff), ("embed_in", "ff"), init="scaled"),
        "w_down": ParamSpec((d_ff, d_model), ("ff", "embed_in"), init="scaled"),
    }


def swiglu(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g) * u
    h = rt.shard(h, "batch", "sp", "ff_act")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return rt.shard(y, "batch", "sp", None)


def gelu_mlp_specs(d_model: int, d_ff: int, bias: bool = True) -> dict:
    s = {
        "w_in": ParamSpec((d_model, d_ff), ("embed_in", "ff"), init="scaled"),
        "w_out": ParamSpec((d_ff, d_model), ("ff", "embed_in"), init="scaled"),
    }
    if bias:
        s["b_in"] = ParamSpec((d_ff,), ("ff",), init="zeros")
        s["b_out"] = ParamSpec((d_model,), (None,), init="zeros")
    return s


def gelu_mlp(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    if "b_in" in p:
        h = h + p["b_in"]
    h = jax.nn.gelu(h)
    h = rt.shard(h, "batch", "sp", "ff_act")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_out"])
    if "b_out" in p:
        y = y + p["b_out"]
    return rt.shard(y, "batch", "sp", None)


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------


def embed_specs(vocab_padded: int, d_model: int) -> dict:
    """Untied: lookup table sharded on its EMBED dim (gathers stay local);
    unembedding sharded on VOCAB (logits + loss stay vocab-sharded)."""
    return {
        "tok": ParamSpec((vocab_padded, d_model), (None, "table_embed")),
        "unembed": ParamSpec(
            (d_model, vocab_padded), (None, "vocab"), init="scaled"
        ),
    }


def embed(rt: Runtime, p: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return rt.shard(x, "batch", "sp", None)


def unembed(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"])
    return rt.shard(logits, "batch", "sp", "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab_real: int) -> jax.Array:
    """Mean NLL over (possibly vocab-sharded) logits; fused one-hot gold
    extraction so GSPMD never all-gathers the vocab dim; padded tail masked.
    """
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    if vocab_real < V:
        mask = jnp.arange(V) < vocab_real
        lg = jnp.where(mask, lg, -1e9)
    logz = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(labels, V, dtype=lg.dtype)
    gold = jnp.sum(lg * onehot, axis=-1)
    return jnp.mean(logz - gold)
