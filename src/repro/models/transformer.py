"""Unified decoder-only LM — covers the dense, MoE and VLM-backbone archs.

One config class parameterizes: GQA/MQA attention (RoPE, optional sliding
window, optional qkv bias), RMSNorm/LayerNorm, SwiGLU/GELU MLP or a MoE
layer, an optional bidirectional prefix (paligemma's SigLIP stub embeds),
and an optional gemma-style sqrt(d) embedding scale.

Layers are stacked with ``jax.lax.scan`` over a leading layer dim (compile
time O(1) in depth) and rematerialized per the configured policy.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from .moe import MoEConfig, moe_apply, moe_specs
from .param import ParamSpec, cast_floats, round_up, stack_specs


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str = "rms"              # rms | ln
    rms_eps: float = 1e-6          # RMSNorm epsilon
    act: str = "swiglu"            # swiglu | gelu
    window: int | None = None      # sliding-window attention
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    moe: MoEConfig | None = None
    prefix_len: int = 0            # VLM/audio stub prefix (train/prefill)
    embed_scale: bool = False      # gemma: x *= sqrt(d_model)
    remat_policy: str = "nothing"  # nothing | dots
    attn_impl: str = "reference"   # reference | blocked (flash-style)
    unroll: bool = False           # python-loop layers (dry-run cost probes)
    dtype: Any = jnp.bfloat16

    @property
    def vocab_padded(self) -> int:
        return round_up(self.vocab_size, 256)

    def attn(self, prefix: int = 0) -> L.AttnConfig:
        return L.AttnConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            causal=True,
            window=self.window,
            rope_theta=self.rope_theta,
            qkv_bias=self.qkv_bias,
            prefix_len=prefix,
            impl=self.attn_impl,
        )

    @property
    def param_count(self) -> int:
        from .param import param_count

        return param_count(lm_specs(self))


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _norm_specs(cfg: LMConfig) -> Any:
    return (
        L.rmsnorm_spec(cfg.d_model) if cfg.norm == "rms" else L.layernorm_specs(cfg.d_model)
    )


def _apply_norm(cfg: LMConfig, p: Any, x: jax.Array) -> jax.Array:
    with jax.named_scope("norm"):
        return L.rmsnorm(p, x, cfg.rms_eps) if cfg.norm == "rms" else L.layernorm(p, x)


def block_specs(cfg: LMConfig) -> dict:
    specs = {
        "ln1": _norm_specs(cfg),
        "attn": L.attn_specs(cfg.attn()),
        "ln2": _norm_specs(cfg),
    }
    if cfg.moe is not None:
        specs["moe"] = moe_specs(cfg.d_model, cfg.moe)
    elif cfg.act == "swiglu":
        specs["mlp"] = L.swiglu_specs(cfg.d_model, cfg.d_ff)
    else:
        specs["mlp"] = L.gelu_mlp_specs(cfg.d_model, cfg.d_ff)
    return specs


def lm_specs(cfg: LMConfig) -> dict:
    return {
        "embed": L.embed_specs(cfg.vocab_padded, cfg.d_model),
        "blocks": stack_specs(block_specs(cfg), cfg.n_layers),
        "final_norm": _norm_specs(cfg),
    }


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _block(
    rt: L.Runtime,
    cfg: LMConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_pos: jax.Array | None = None,
    prefix: int = 0,
    cache_layer: jax.Array | None = None,
):
    h = _apply_norm(cfg, p["ln1"], x)
    a, new_cache = L.attention(
        rt, p["attn"], h, cfg.attn(prefix), positions, cache, cache_pos,
        cache_layer=cache_layer,
    )
    x = x + a
    h = _apply_norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if cfg.moe is not None:
            m, aux = moe_apply(rt, p["moe"], h, cfg.moe)
        elif cfg.act == "swiglu":
            m = L.swiglu(rt, p["mlp"], h)
        else:
            m = L.gelu_mlp(rt, p["mlp"], h)
    x = x + m
    x = rt.shard(x, "batch", "sp", None)
    return x, new_cache, aux


def _scan_or_unroll(cfg, body, init, xs):
    """lax.scan, or a python loop when cfg.unroll (cost probes)."""
    if not cfg.unroll:
        return jax.lax.scan(body, init, xs)
    carry = init
    ys = []
    n = jax.tree.leaves(xs)[0].shape[0]
    for i in range(n):
        xi = jax.tree.map(lambda t: t[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    stacked = jax.tree.map(lambda *a: jnp.stack(a, axis=0), *ys)
    return carry, stacked


def _remat(cfg: LMConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    policy = (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if cfg.remat_policy == "dots"
        else jax.checkpoint_policies.nothing_saveable
    )
    return jax.checkpoint(fn, policy=policy)


def forward(
    rt: L.Runtime,
    cfg: LMConfig,
    params: dict,
    tokens: jax.Array,                       # (B, S)
    prefix_embeds: jax.Array | None = None,  # (B, P, D) modality stub
) -> tuple[jax.Array, jax.Array]:
    """Training/scoring forward.  Returns (logits, aux_loss)."""
    params = cast_floats(params, cfg.dtype)
    x = L.embed(rt, params["embed"], tokens)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    prefix = 0
    if prefix_embeds is not None:
        prefix = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        x = rt.shard(x, "batch", "sp", None)
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(carry, lp):
        h, aux = carry
        h, _, a = _block(rt, cfg, lp, h, positions, prefix=prefix)
        return (h, aux + a), None

    carry = (x.astype(cfg.dtype), jnp.zeros((), jnp.float32))
    with jax.named_scope("layer_stack"):
        if cfg.unroll:
            rb = _remat(cfg, body)
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda t: t[i], params["blocks"])
                carry, _ = rb(carry, lp)
            x, aux = carry
        else:
            (x, aux), _ = jax.lax.scan(_remat(cfg, body), carry, params["blocks"])
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(rt, params["embed"], x)
    if prefix:
        logits = logits[:, prefix:]
    return logits, aux


def loss_fn(
    rt: L.Runtime,
    cfg: LMConfig,
    params: dict,
    batch: dict,
) -> jax.Array:
    logits, aux = forward(
        rt, cfg, params, batch["tokens"], batch.get("prefix_embeds")
    )
    return L.cross_entropy(logits, batch["labels"], cfg.vocab_size) + aux


# ---------------------------------------------------------------------------
# serving: prefill + decode with a scanned KV cache
# ---------------------------------------------------------------------------


def cache_specs(cfg: LMConfig, batch: int, max_len: int) -> dict:
    return L.init_kv_cache(cfg.attn(), batch, max_len, cfg.n_layers, cfg.dtype)


def prefill(
    rt: L.Runtime,
    cfg: LMConfig,
    params: dict,
    tokens: jax.Array,          # (B, S)
    cache: dict,                # {"k","v"}: (L, B, Smax, K, Dh)
    prefix_embeds: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Populate the cache positions [0, S); return last-token logits."""
    params = cast_floats(params, cfg.dtype)
    x = L.embed(rt, params["embed"], tokens)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    prefix = 0
    if prefix_embeds is not None:
        prefix = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    zero = jnp.zeros((), jnp.int32)

    def body(h, xs):
        lp, ck, cv = xs
        h, new_cache, _ = _block(
            rt, cfg, lp, h, positions, cache=(ck, cv), cache_pos=zero, prefix=prefix
        )
        return h, new_cache

    with jax.named_scope("layer_stack"):
        x, (ck, cv) = _scan_or_unroll(cfg, body, x.astype(cfg.dtype), (params["blocks"], cache["k"], cache["v"]))
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(rt, params["embed"], x[:, -1:])
    return logits, {"k": ck, "v": cv}


def decode_step(
    rt: L.Runtime,
    cfg: LMConfig,
    params: dict,
    tokens: jax.Array,          # (B, 1) the newest token ids
    cache: dict,
    pos: jax.Array,             # scalar int32: current write position
) -> tuple[jax.Array, dict]:
    """One autoregressive step against a populated cache.

    The stacked cache rides in the layer scan's carry and each layer writes
    only its new row into it, so a donated cache is updated in place:
    scanned as ``xs``/``ys`` it would be restacked into new buffers and
    copied whole every step.
    """
    params = cast_floats(params, cfg.dtype)
    x = L.embed(rt, params["embed"], tokens)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    positions = pos[None] if pos.ndim == 0 else pos

    def body(carry, xs):
        h, kv = carry
        lp, layer = xs
        h, kv, _ = _block(
            rt, cfg, lp, h, positions, cache=kv, cache_pos=pos, cache_layer=layer
        )
        return (h, kv), None

    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    with jax.named_scope("layer_stack"):
        (x, (ck, cv)), _ = _scan_or_unroll(
            cfg, body, (x.astype(cfg.dtype), (cache["k"], cache["v"])), (params["blocks"], layers)
        )
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(rt, params["embed"], x)
    return logits, {"k": ck, "v": cv}
