"""``dense_lm``'s float32 training reference with its state spread over
several devices.

The arithmetic is ``dense_lm``'s, unchanged: the same weights from the
seed, the same plain float32 forward, loss and gradients at ``HIGHEST``,
the same AdamW, the same readings.  Only where the tensors live differs:
every weight, its gradient sum and its AdamW moments are split over the
given devices along one axis of the tensor (``SPLIT``), so that a model
whose float32 state does not fit one chip (granite-8b at 8 layers: 34 GB)
can be followed.  The axes are those of tensor parallelism, so that the
compiler keeps each layer's matmuls on the devices that hold its slices:
q/k/v, gate and up by their outputs, the attention output and down
projections by their inputs, both embeddings by the vocabulary; norm
weights by their width.

Nothing here is imported from ``models/`` or ``parallel/``; the mesh is a
plain one-axis ``jax.sharding.Mesh`` of the devices.

``halves=True`` plants the fault of a data-parallel step whose gradient
reduction over the data axis is left out: each data half's share of
every tensor (ZeRO-1's share of the optimizer state, ``zero_axis``) is
updated from the gradient of its own half of the batch alone.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference import dense_lm

AXIS = "chips"
SPLIT = {
    "blocks.attn.wk": 2, "blocks.attn.wo": 1, "blocks.attn.wq": 2, "blocks.attn.wv": 2,
    "blocks.ln1": 1, "blocks.ln2": 1,
    "blocks.mlp.w_down": 1, "blocks.mlp.w_gate": 2, "blocks.mlp.w_up": 2,
    "embed.tok": 0, "embed.unembed": 1, "final_norm": 0,
}


def shardings(model: dict, devices) -> tuple:
    """(leaf name, ``NamedSharding``) of every weight, in ``dense_lm.LEAVES``
    order: split over ``devices`` along the leaf's ``SPLIT`` axis."""
    mesh = Mesh(np.asarray(devices), (AXIS,))
    out = []
    for name, shape, _ in dense_lm.leaf_specs(model):
        axis = SPLIT[name]
        if shape[axis] % len(devices):
            raise ValueError(f"{name} {shape}: axis {axis} does not divide over "
                             f"{len(devices)} devices")
        out.append((name, NamedSharding(mesh, P(*([None] * axis + [AXIS])))))
    return tuple(out)


def zero_axis(name: str, shape: tuple) -> int:
    """The axis along which ZeRO-1 splits a tensor's optimizer state over
    two data halves, by the program's rule: the first past the stacked
    layers' axis that tensor parallelism leaves whole and two divides,
    else the first that two divides."""
    first = 1 if name.startswith("blocks.") else 0
    axes = [i for i in range(first, len(shape)) if shape[i] % 2 == 0]
    return next((i for i in axes if i != SPLIT[name]), axes[0])


def _place(tree: dict, placed: tuple) -> dict:
    return {k: jax.lax.with_sharding_constraint(tree[k], s) for k, s in placed}


@partial(jax.jit, static_argnums=(0, 1))
def _make_weights(model_items, placed, key):
    """``dense_lm.make_weights``, each leaf made on the devices that hold it."""
    return _place(dense_lm.make_weights(dict(model_items), key), placed)


@partial(jax.jit, static_argnums=(0,))
def _to_f32(placed, weights):
    return _place({k: v.astype(jnp.float32) for k, v in weights.items()}, placed)


@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))
def _accumulate(model_items, precision, placed, weights, acc, tokens, labels):
    nll, grads = dense_lm._accumulate(model_items, precision, weights, acc, tokens, labels)
    return nll, _place(grads, placed)


@partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(5,))
def _accumulate_half(model_items, precision, placed, half, weights, acc, tokens, labels):
    """``_accumulate`` into data half ``half``'s share of each tensor only
    (0: the first half along ``zero_axis``, 1: the second)."""
    nll, grads = jax.value_and_grad(partial(dense_lm._row_nll, dict(model_items),
                                            dense_lm.matmul(precision)))(weights, tokens, labels)

    def share(name, g):
        axis = zero_axis(name, g.shape)
        first = jax.lax.broadcasted_iota(jnp.int32, g.shape, axis) < g.shape[axis] // 2
        return jnp.where(first == (half == 0), g, 0.0)

    return nll + acc[0], _place({k: acc[1][k] + share(k, g) for k, g in grads.items()}, placed)


def loss_and_grads(model: dict, placed: tuple, weights: dict, tokens: np.ndarray,
                   labels: np.ndarray, precision: str = dense_lm.F32,
                   block_rows: int = 1, halves: bool = False):
    """``dense_lm.loss_and_grads`` with the gradient sums kept split as the
    weights are; with ``halves``, each data half's share of a tensor takes
    the mean gradient of that half's rows alone."""
    items = dense_lm.arch(model)
    rows = len(tokens)
    if halves and (rows // 2) % block_rows:
        raise ValueError(f"blocks of {block_rows} rows straddle the halves of {rows}")
    acc = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, weights))
    for i in range(0, rows, block_rows):
        block = (jnp.asarray(tokens[i:i + block_rows]), jnp.asarray(labels[i:i + block_rows]))
        if halves:
            acc = _accumulate_half(items, precision, placed, int(i >= rows // 2), weights, acc,
                                   *block)
        else:
            acc = _accumulate(items, precision, placed, weights, acc, *block)
    count = tokens.size // 2 if halves else tokens.size
    nll, grads = acc
    return float(nll) / tokens.size, jax.tree.map(lambda g: g / count, grads)


def train_readings(model: dict, opt: dict, key: jax.Array, batches: list, devices,
                   precision: str = dense_lm.F32, block_rows: int = 1,
                   halves: bool = False) -> dict:
    """``dense_lm.train_readings`` with weights, gradients and AdamW state
    split over ``devices``.  The change is taken against the bfloat16
    weights as made, which the float32 start equals.  ``halves`` leaves
    the gradients' reduction over the two data halves out
    (``loss_and_grads``)."""
    placed = shardings(model, devices)
    w0 = _make_weights(dense_lm.arch(model), placed, key)
    w = _to_f32(placed, w0)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    hyper = tuple(jnp.float32(opt[k]) for k in ("b1", "b2", "eps", "weight_decay"))
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches):
        loss, grads = loss_and_grads(model, placed, w, tokens, labels, precision, block_rows,
                                     halves)
        norms = dense_lm._norms(grads)
        gnorm = math.sqrt(sum(float(x) ** 2 for x in norms.values()))
        scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
        if step == 0:
            first_grad = {k: float(x) * scale for k, x in norms.items()}
        w, m, v = dense_lm._adamw(w, m, v, grads, jnp.float32(scale),
                                  jnp.float32(dense_lm.learning_rate(opt, step)),
                                  jnp.float32(step + 1), hyper)
        losses.append(loss)
        del grads
    change = dense_lm._change_norms(w, w0)
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": {k: float(x) for k, x in change.items()}}
