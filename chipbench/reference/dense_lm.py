"""Plain float32 reference of the dense decoder-only LM the configurations
describe, independent of the program's ``models/``.

Architecture, as the configuration file states it: token embedding
(untied from the head), ``num_hidden_layers`` pre-norm blocks of RMSNorm,
grouped-query causal self-attention with rotary embeddings (the two
halves of each head rotated against each other) and a SwiGLU MLP, a final
RMSNorm and a linear head.  Every matmul runs in float32 at ``HIGHEST``
precision, since a TPU otherwise multiplies float32 in bfloat16.

Weights are made from the seed by the recipe the configurations are
defined with: the 12 weight tensors, in the order of ``LEAVES``, each
stacked over the layers where it belongs to a block, draw from
``jax.random.split(key, 12)`` in turn; "scaled" ones are a standard
normal over the square root of their fan-in (the next-to-last dimension),
the token table a normal times 0.02, norm weights ones; all are rounded
to bfloat16, the type they are served in.  The vocabulary of the two
embedding tensors is padded to a multiple of 256 rows; the padding is
never read as a logit.

``Precision`` selects the arithmetic: ``F32`` is the reference, ``FP8``
the control that must fail the comparison: every matmul operand (and, in
training, every cotangent entering a matmul) rounded to float8 e4m3 with
a per-tensor scale, the step below the configuration's bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32, FP8 = "f32", "fp8"
FP8_MAX = 448.0  # largest finite float8_e4m3fn

LEAVES = (
    "blocks.attn.wk", "blocks.attn.wo", "blocks.attn.wq", "blocks.attn.wv",
    "blocks.ln1", "blocks.ln2",
    "blocks.mlp.w_down", "blocks.mlp.w_gate", "blocks.mlp.w_up",
    "embed.tok", "embed.unembed", "final_norm",
)


ARCH_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
             "rms_norm_eps", "rope_theta")


def arch(model: dict) -> tuple:
    """The keys of a configuration that the arithmetic reads, hashable."""
    return tuple((k, model[k]) for k in ARCH_KEYS)


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def leaf_specs(model: dict) -> list[tuple[str, tuple, str]]:
    L, D = model["num_hidden_layers"], model["hidden_size"]
    N, K, Dh = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    F, Vp = model["intermediate_size"], padded_vocab(model["vocab_size"])
    shapes = {
        "blocks.attn.wk": ((L, D, K * Dh), "scaled"),
        "blocks.attn.wo": ((L, N * Dh, D), "scaled"),
        "blocks.attn.wq": ((L, D, N * Dh), "scaled"),
        "blocks.attn.wv": ((L, D, K * Dh), "scaled"),
        "blocks.ln1": ((L, D), "ones"),
        "blocks.ln2": ((L, D), "ones"),
        "blocks.mlp.w_down": ((L, F, D), "scaled"),
        "blocks.mlp.w_gate": ((L, D, F), "scaled"),
        "blocks.mlp.w_up": ((L, D, F), "scaled"),
        "embed.tok": ((Vp, D), "normal"),
        "embed.unembed": ((D, Vp), "scaled"),
        "final_norm": ((D,), "ones"),
    }
    return [(name, *shapes[name]) for name in LEAVES]


def make_weights(model: dict, key: jax.Array) -> dict:
    """All weights in bfloat16, made on the device in one program."""
    specs = leaf_specs(model)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for (name, shape, init), k in zip(specs, keys):
            if init == "ones":
                x = jnp.ones(shape, jnp.float32)
            elif init == "scaled":
                x = jax.random.normal(k, shape, jnp.float32) * (1.0 / math.sqrt(shape[-2]))
            else:
                x = jax.random.normal(k, shape, jnp.float32) * 0.02
            out[name] = x.astype(jnp.bfloat16)
        return out

    return make(key)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(x):
    return x


_fp8_cotangent.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def matmul(precision: str):
    if precision == F32:
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == FP8:
        return lambda spec, a, b: _fp8_cotangent(jnp.einsum(
            spec, _fp8_operand(a), _fp8_operand(b), precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """x: (B, S, H, Dh); rotates the first half of each head against the
    second by angle position * theta**(-i / half)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(model: dict, mm, x, w: dict, positions):
    """One pre-norm block; x (B, S, D) float32, w this layer's weights."""
    B, S, _ = x.shape
    N, K, Dh = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = rmsnorm(x, w["blocks.ln1"], eps)
    q = mm("bsd,dp->bsp", h, w["blocks.attn.wq"]).reshape(B, S, N, Dh)
    k = mm("bsd,dp->bsp", h, w["blocks.attn.wk"]).reshape(B, S, K, Dh)
    v = mm("bsd,dp->bsp", h, w["blocks.attn.wv"]).reshape(B, S, K, Dh)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    # query head n reads key/value head n // (N // K)
    k, v = jnp.repeat(k, N // K, axis=2), jnp.repeat(v, N // K, axis=2)
    scores = mm("bqnd,bknd->bnqk", q, k) / math.sqrt(Dh)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = mm("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm("bsp,pd->bsd", attn.reshape(B, S, N * Dh), w["blocks.attn.wo"])
    h = rmsnorm(x, w["blocks.ln2"], eps)
    gate = mm("bsd,df->bsf", h, w["blocks.mlp.w_gate"])
    up = mm("bsd,df->bsf", h, w["blocks.mlp.w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["blocks.mlp.w_down"])


def hidden(model: dict, mm, weights: dict, tokens, remat: bool = False):
    """Final-norm hidden states (B, S, D) in float32; layer weights are
    cast to float32 one layer at a time."""
    positions = jnp.arange(tokens.shape[1])
    x = weights["embed.tok"][tokens].astype(jnp.float32)
    stacked = {k: v for k, v in weights.items() if k.startswith("blocks.")}

    def body(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return block(model, mm, x, w, positions), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    return rmsnorm(x, weights["final_norm"].astype(jnp.float32), model["rms_norm_eps"])


def logits(model: dict, mm, weights: dict, x):
    """Logits over the real vocabulary for hidden states x (..., D)."""
    head = weights["embed.unembed"][:, : model["vocab_size"]].astype(jnp.float32)
    return mm("bsd,dv->bsv", x, head)


# ---------------------------------------------------------------------------
# serving: logits at the served positions
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 1, 4))
def _tail_logits(model_items, precision, weights, tokens, n):
    model = dict(model_items)
    mm = matmul(precision)
    x = hidden(model, mm, weights, tokens)
    return logits(model, mm, weights, x[:, -n:])


def served_logits(model: dict, weights: dict, sequences: np.ndarray, n: int,
                  precision: str = F32, block_rows: int = 1) -> np.ndarray:
    """Logits (R, n, V) at the last ``n`` positions of each row of
    ``sequences`` (R, S), computed ``block_rows`` rows at a time."""
    items = arch(model)
    out = []
    for i in range(0, len(sequences), block_rows):
        rows = jnp.asarray(sequences[i:i + block_rows])
        out.append(np.asarray(_tail_logits(items, precision, weights, rows, n)))
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW steps
# ---------------------------------------------------------------------------


def _row_nll(model, mm, weights, tokens, labels):
    """Summed next-token NLL of rows (b, S) in float32."""
    x = hidden(model, mm, weights, tokens, remat=True)
    lg = logits(model, mm, weights, x)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _accumulate(model_items, precision, weights, acc, tokens, labels):
    model = dict(model_items)
    nll, grads = jax.value_and_grad(partial(_row_nll, model, matmul(precision)))(
        weights, tokens, labels)
    return nll + acc[0], jax.tree.map(jnp.add, acc[1], grads)


def loss_and_grads(model: dict, weights: dict, tokens: np.ndarray,
                   labels: np.ndarray, precision: str = F32,
                   block_rows: int = 1):
    """Mean NLL over all tokens and its gradients, ``block_rows`` rows at a
    time, summed in float32."""
    items = arch(model)
    acc = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, weights))
    for i in range(0, len(tokens), block_rows):
        acc = _accumulate(items, precision, weights, acc,
                          jnp.asarray(tokens[i:i + block_rows]),
                          jnp.asarray(labels[i:i + block_rows]))
    count = tokens.size
    nll, grads = acc
    return float(nll) / count, jax.tree.map(lambda g: g / count, grads)


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``; ``step`` counts
    from 0."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    span = max(opt["decay_steps"] - opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@jax.jit
def _change_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(new[k] - old[k]))) for k in new}


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(w, m, v, grads, scale, lr, t, hyper):
    b1, b2, eps, wd = hyper
    def one(w, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return w - lr * (mh / (jnp.sqrt(vh) + eps) + wd * w), m, v
    out = {k: one(w[k], m[k], v[k], grads[k]) for k in w}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def train_readings(model: dict, opt: dict, key: jax.Array, batches: list,
                   precision: str = F32, block_rows: int = 1) -> dict:
    """Run AdamW from the seeded weights over ``batches`` [(tokens, labels)]
    and read what the comparison needs: the loss of each step, the norm of
    each tensor's first clipped gradient, and the norm of each tensor's
    change over all the steps."""
    w0 = make_weights(model, key)
    w = {k: v.astype(jnp.float32) for k, v in w0.items()}
    del w0
    start = {k: jnp.copy(v) for k, v in w.items()}
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    hyper = tuple(jnp.float32(opt[k]) for k in ("b1", "b2", "eps", "weight_decay"))
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches):
        loss, grads = loss_and_grads(model, w, tokens, labels, precision, block_rows)
        norms = _norms(grads)
        gnorm = math.sqrt(sum(float(x) ** 2 for x in norms.values()))
        scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
        if step == 0:
            first_grad = {k: float(x) * scale for k, x in norms.items()}
        w, m, v = _adamw(w, m, v, grads, jnp.float32(scale),
                         jnp.float32(learning_rate(opt, step)), jnp.float32(step + 1), hyper)
        losses.append(loss)
        del grads
    change = _change_norms(w, start)
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": {k: float(x) for k, x in change.items()}}
