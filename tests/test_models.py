"""Per-architecture smoke tests: reduced configs, one forward/train step +
one decode step on CPU, asserting shapes and finiteness (harness deliverable
f), plus model-level invariants (causality, prefill/decode consistency)."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, load
from repro.models.api import SHAPES, ShapeCell
from repro.models.layers import Runtime
from repro.models.param import param_count, tree_init

RT = Runtime(rules=None)
KEY = jax.random.PRNGKey(0)
CELL = ShapeCell("smoke", "train", 32, 2)
DECODE_CELL = ShapeCell("smoke_decode", "decode", 64, 2)


def make_batch(harness, cell):
    batch = {}
    for k, s in harness.train_input_specs(cell).items():
        if s.dtype == jnp.int32:
            batch[k] = jnp.asarray(
                np.random.default_rng(0).integers(0, 64, s.shape), jnp.int32
            )
        else:
            batch[k] = jnp.full(s.shape, 0.01, s.dtype)
    return batch


@pytest.fixture(scope="module")
def harnesses():
    return {a: load(a, smoke=True) for a in ARCH_IDS}


@pytest.fixture(scope="module")
def all_params(harnesses):
    return {a: tree_init(h.param_specs(), KEY) for a, h in harnesses.items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestSmoke:
    def test_train_step_loss_finite(self, arch, harnesses, all_params):
        h = harnesses[arch]
        params = all_params[arch]
        batch = make_batch(h, CELL)
        loss, grads = jax.jit(jax.value_and_grad(h.loss(RT)))(params, batch)
        assert np.isfinite(float(loss))
        gnorm = sum(
            float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in jax.tree.leaves(grads)
        )
        assert np.isfinite(gnorm) and gnorm > 0

    def test_decode_step_shapes(self, arch, harnesses, all_params):
        h = harnesses[arch]
        params = all_params[arch]
        state = tree_init(h.serve_state_specs(DECODE_CELL), KEY)
        tokens = jnp.zeros((2, 1), jnp.int32) + 3
        pos = jnp.asarray(5, jnp.int32)
        logits, new_state = jax.jit(h.decode(RT))(params, state, tokens, pos)
        assert logits.shape[0] == 2 and logits.shape[1] == 1
        assert logits.shape[2] >= h.cfg.vocab_size
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        # state structure preserved
        assert jax.tree.structure(new_state) == jax.tree.structure(state)

    def test_skip_matrix_matches_design(self, arch, harnesses):
        h = harnesses[arch]
        skip = h.skip_reason("long_500k")
        if arch in ("zamba2_1_2b", "rwkv6_1_6b", "mixtral_8x22b"):
            assert skip is None
        else:
            assert skip is not None
        assert h.skip_reason("train_4k") is None


class TestInvariants:
    def test_causality_dense(self):
        """perturbing a future token must not change earlier logits"""
        h = load("granite_8b", smoke=True)
        params = tree_init(h.param_specs(), KEY)
        from repro.models import transformer

        tok1 = jnp.zeros((1, 16), jnp.int32) + 5
        tok2 = tok1.at[0, 12].set(9)
        lg1, _ = transformer.forward(RT, h.cfg, params, tok1)
        lg2, _ = transformer.forward(RT, h.cfg, params, tok2)
        np.testing.assert_allclose(
            np.asarray(lg1[:, :12], np.float32),
            np.asarray(lg2[:, :12], np.float32),
            atol=1e-5,
        )
        assert not np.allclose(
            np.asarray(lg1[:, 12:], np.float32), np.asarray(lg2[:, 12:], np.float32)
        )

    def test_rms_eps_reaches_the_norms(self):
        """the config's RMSNorm epsilon is the one the forward uses"""
        h = load("granite_8b", smoke=True)
        params = tree_init(h.param_specs(), KEY)
        from repro.models import transformer

        tokens = jnp.asarray(np.random.default_rng(2).integers(0, 64, (1, 16)), jnp.int32)
        base, _ = transformer.forward(RT, h.cfg, params, tokens)
        same, _ = transformer.forward(RT, h.clone(rms_eps=1e-6).cfg, params, tokens)
        wide, _ = transformer.forward(RT, h.clone(rms_eps=1.0).cfg, params, tokens)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
        assert not np.allclose(np.asarray(base, np.float32), np.asarray(wide, np.float32))

    def test_prefill_decode_consistency(self):
        """prefill(S tokens) then decode == prefill(S+1 tokens) logits"""
        h = load("granite_8b", smoke=True)
        params = tree_init(h.param_specs(), KEY)
        from repro.models import transformer

        S = 8
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, 64, (2, S + 1)), jnp.int32
        )
        cell = ShapeCell("t", "decode", S + 4, 2)
        cache = tree_init(h.serve_state_specs(cell), KEY)
        lg_pre, cache = transformer.prefill(RT, h.cfg, params, tokens[:, :S], cache)
        lg_dec, _ = transformer.decode_step(
            RT, h.cfg, params, tokens[:, S:], cache, jnp.asarray(S, jnp.int32)
        )
        # reference: full forward over S+1 tokens, last position
        lg_full, _ = transformer.forward(RT, h.cfg, params, tokens)
        np.testing.assert_allclose(
            np.asarray(lg_dec[:, -1], np.float32),
            np.asarray(lg_full[:, -1], np.float32),
            atol=3e-2,  # bf16 cache
        )

    def test_decode_steps_match_forward(self):
        """three donated decode steps after prefill each match forward over
        the same prefix, and the unrolled layer loop gives the scan's step"""
        h = load("granite_8b", smoke=True)
        params = tree_init(h.param_specs(), KEY)
        from repro.models import transformer

        S, steps = 8, 3
        tokens = jnp.asarray(
            np.random.default_rng(4).integers(0, 64, (2, S + steps)), jnp.int32
        )
        cell = ShapeCell("t", "decode", S + steps + 2, 2)
        cache = tree_init(h.serve_state_specs(cell), KEY)
        _, cache = transformer.prefill(RT, h.cfg, params, tokens[:, :S], cache)
        step = jax.jit(partial(transformer.decode_step, RT, h.cfg), donate_argnums=(2,))
        unrolled = jax.jit(partial(transformer.decode_step, RT, h.clone(unroll=True).cfg))
        for i in range(steps):
            tok, pos = tokens[:, S + i : S + i + 1], jnp.asarray(S + i, jnp.int32)
            out_unrolled = unrolled(params, tok, cache, pos)
            lg, cache = step(params, tok, cache, pos)
            lg_full, _ = transformer.forward(RT, h.cfg, params, tokens[:, : S + i + 1])
            # bf16 cache; the two loops fuse, and so round, differently
            for got, want in zip(
                jax.tree.leaves((lg[:, -1], out_unrolled)),
                jax.tree.leaves((lg_full[:, -1], (lg, cache))),
            ):
                np.testing.assert_allclose(
                    np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
                )

    def test_decode_carries_cache_in_place(self):
        """the donated stacked cache goes through the layer loop as one
        carried pair and comes back in the donated buffers, with no copy of
        it round the loop and no restacked copy of it beside it

        Only the entry computation is checked here: the CPU backend performs
        a bfloat16 dynamic-update-slice in float32 over the whole buffer, so
        inside the loop it copies what the TPU updates in place
        (test_tpu_compile.py checks the whole decode program for the TPU)."""
        h = load("granite_3_2b", smoke=True)
        params = jax.eval_shape(lambda: tree_init(h.param_specs(), KEY))
        cache = jax.eval_shape(
            lambda: tree_init(h.serve_state_specs(ShapeCell("t", "decode", 40, 2)), KEY)
        )
        args = (params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
        hlo = jax.jit(h.decode(RT), donate_argnums=(1,)).lower(*args).compile().as_text()
        stacked = re.compile(
            r"\b[a-z]+[0-9]*\[%s\]" % ",".join(map(str, cache["k"].shape))
        )
        lines = hlo.splitlines()
        start = next(i for i, line in enumerate(lines) if line.startswith("ENTRY "))
        entry = lines[start + 1 : lines.index("}", start)]
        made = [line.split(" = ", 1)[1] for line in entry if " = " in line]
        made = [m for m in made if stacked.match(m)]
        assert made and all(
            re.search(r"\} (parameter|get-tuple-element)\(", m) for m in made
        ), made
        loop = next(m for m in (line.split(" = ", 1)[-1] for line in entry)
                    if re.search(r"\) while\(", m))
        assert len(stacked.findall(loop.split(" while(")[0])) == 2
        n = len(jax.tree.leaves(params))
        alias = re.search(r"input_output_alias=\{(.*?)\s\}", hlo).group(1)
        pairs = dict(re.findall(r"\{(\d+)\}: \((\d+),", alias))
        assert pairs == {"1": str(n), "2": str(n + 1)}

    def test_rwkv_decode_matches_forward(self):
        h = load("rwkv6_1_6b", smoke=True)
        params = tree_init(h.param_specs(), KEY)
        from repro.models import rwkv_lm

        S = 12
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, 64, (1, S)), jnp.int32
        )
        lg_full = rwkv_lm.forward(RT, h.cfg, params, tokens)
        # recurrent: feed tokens one by one
        state = tree_init(h.serve_state_specs(ShapeCell("t", "decode", S, 1)), KEY)
        outs = []
        for t in range(S):
            lg, state = rwkv_lm.decode_step(
                RT, h.cfg, params, tokens[:, t : t + 1], state, jnp.asarray(t)
            )
            outs.append(lg[:, 0])
        lg_rec = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(lg_rec, np.float32), np.asarray(lg_full, np.float32),
            atol=5e-2,
        )

    def test_sliding_window_limits_context(self):
        """starcoder2 SWA: tokens beyond the window have no influence"""
        h = load("starcoder2_7b", smoke=True)   # window=64 in smoke
        params = tree_init(h.param_specs(), KEY)
        from repro.models import transformer

        S = 128
        base = np.random.default_rng(3).integers(0, 64, (1, S))
        t1 = jnp.asarray(base, jnp.int32)
        pert = base.copy()
        pert[0, 0] = (pert[0, 0] + 7) % 64
        t2 = jnp.asarray(pert, jnp.int32)
        lg1, _ = transformer.forward(RT, h.cfg, params, t1)
        lg2, _ = transformer.forward(RT, h.cfg, params, t2)
        # with 2 layers x window 64, influence dies beyond ~2*64 tokens
        np.testing.assert_allclose(
            np.asarray(lg1[:, -1], np.float32), np.asarray(lg2[:, -1], np.float32),
            atol=1e-5,
        )

    def test_param_counts_full_configs(self):
        """full (non-smoke) configs land near their nameplate sizes"""
        expect = {
            "granite_8b": (7e9, 10e9),
            "phi4_mini_3_8b": (3e9, 5.5e9),
            "granite_3_2b": (2e9, 3.3e9),
            "starcoder2_7b": (6e9, 9e9),
            "zamba2_1_2b": (0.9e9, 1.9e9),
            "rwkv6_1_6b": (1.3e9, 2.3e9),
            "mixtral_8x22b": (120e9, 160e9),
            "dbrx_132b": (110e9, 150e9),
            "whisper_base": (0.04e9, 0.12e9),
            "paligemma_3b": (2e9, 4e9),
        }
        for arch, (lo, hi) in expect.items():
            n = param_count(load(arch).param_specs())
            assert lo < n < hi, f"{arch}: {n:.3g} params not in ({lo:.2g},{hi:.2g})"
