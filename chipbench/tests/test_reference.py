"""The reference agrees with the program at a smoke configuration: the same
weights from the seed, and in float32 the same logits, loss and
gradients."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import traffic
from conftest import smoke
from reference import dense_lm


@pytest.fixture(scope="module")
def setup():
    model = smoke("granite-3-2b.train.b8s2k")["model"]
    h = program.harness(model)
    key = jax.random.PRNGKey(traffic.seed32(2**31 + 7))
    return model, h, key


def _program_leaves(h, key):
    from repro.models.param import tree_init

    params = tree_init(h.param_specs(), key, dtype=jnp.bfloat16)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {".".join(k.key for k in path): v for path, v in flat}, params


def test_weights_are_the_programs(setup):
    model, h, key = setup
    got, _ = _program_leaves(h, key)
    want = dense_lm.make_weights(model, key)
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(want[name], np.float32), err_msg=name)


def test_float32_logits_loss_and_gradients_agree(setup):
    from repro.models import transformer
    from repro.models.layers import Runtime

    model, h, key = setup
    cfg = dataclasses.replace(h.cfg, dtype=jnp.float32)
    _, params = _program_leaves(h, key)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    rows = traffic.SyntheticSource(2, 24, model["vocab_size"], 5).batch_at(0)
    tokens, labels = rows[:, :-1], rows[:, 1:]
    rt = Runtime(rules=None)

    got, _ = transformer.forward(rt, cfg, params32, jnp.asarray(tokens))
    weights = dense_lm.make_weights(model, key)
    want = dense_lm.served_logits(model, weights, tokens, tokens.shape[1])
    np.testing.assert_allclose(np.asarray(got)[..., : model["vocab_size"]], want,
                               rtol=1e-4, atol=1e-4)

    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    loss, grads = jax.value_and_grad(
        lambda p: transformer.loss_fn(rt, cfg, p, batch))(params32)
    w32 = {k: v.astype(jnp.float32) for k, v in weights.items()}
    ref_loss, ref_grads = dense_lm.loss_and_grads(model, w32, tokens, labels)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for path, g in flat:
        name = ".".join(k.key for k in path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_grads[name]),
                                   rtol=1e-3, atol=1e-6, err_msg=name)


def test_learning_rate_schedule():
    opt = {"lr": 1.0, "warmup_steps": 10, "decay_steps": 110, "min_lr_ratio": 0.1}
    assert dense_lm.learning_rate(opt, 0) == pytest.approx(0.1)
    assert dense_lm.learning_rate(opt, 9) == pytest.approx(1.0)
    assert dense_lm.learning_rate(opt, 60) == pytest.approx(0.55)
    assert dense_lm.learning_rate(opt, 500) == pytest.approx(0.1)
