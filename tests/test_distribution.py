"""Distribution-layer tests: sharding rules, ZeRO-1, optimizer, compression,
checkpoint/restart + elastic restore, data pipeline, fault tolerance."""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or skip-shim
from jax.sharding import PartitionSpec as P

from repro.data.pipeline import DataConfig, Pipeline, SyntheticSource
from repro.checkpoint.manager import CheckpointManager
from repro.models.param import ParamSpec, ShardingRules, tree_init, tree_pspecs
from repro.optim import adamw
from repro.optim.compression import CompressionConfig, compress_grads
from repro.parallel.sharding import make_rules, tree_zero1_pspecs, zero1_pspec
from repro.runtime.elastic import ElasticPlan
from repro.runtime.fault_tolerance import RackFailover, TrainingSupervisor


class TestShardingRules:
    def test_train_rules_seq_shard_wins_over_ff(self):
        rules = make_rules(multi_pod=False, sp=True)
        # activation (batch, sp, ff_act): sp takes "model", ff dropped
        spec = rules.pspec(("batch", "sp", "ff_act"))
        assert spec == P("data", "model")

    def test_decode_rules_ff_gets_model(self):
        rules = make_rules(multi_pod=False, sp=False)
        spec = rules.pspec(("batch", "sp", "ff_act"))
        assert spec == P("data", None, "model")

    def test_multipod_batch_spans_pod_and_data(self):
        rules = make_rules(multi_pod=True, sp=True)
        spec = rules.pspec(("batch", "sp", None))
        assert spec == P(("pod", "data"), "model")

    def test_zero1_adds_dp_axis_on_free_dim(self):
        rules = make_rules(multi_pod=False, sp=True)
        s = ParamSpec((4096, 1024), ("embed_in", "ff"))
        ps = zero1_pspec(s, rules, {"data": 16, "model": 16})
        assert ps == P("data", "model")

    def test_zero1_skips_layer_dim(self):
        rules = make_rules(multi_pod=False, sp=True)
        s = ParamSpec((36, 4096, 1024), ("layers", "embed_in", "ff"))
        ps = zero1_pspec(s, rules, {"data": 16, "model": 16})
        assert ps == P(None, "data", "model")

    @given(st.integers(1, 4), st.integers(1, 4))
    @settings(max_examples=20, deadline=None)
    def test_pspec_never_reuses_axis(self, a, b):
        rules = make_rules(multi_pod=True, sp=True)
        logical = ("batch", "sp", "ff_act", "vocab")[: a + b]
        spec = rules.pspec(tuple(logical))
        used = []
        for entry in spec:
            if entry is None:
                continue
            used.extend([entry] if isinstance(entry, str) else list(entry))
        assert len(used) == len(set(used))


class TestOptimizer:
    def _setup(self):
        specs = {
            "w": ParamSpec((64, 32), (None, None)),
            "b": ParamSpec((32,), (None,), init="zeros"),
        }
        params = tree_init(specs, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
        return params, adamw.init_opt_state(params)

    def test_step_reduces_quadratic_loss(self):
        params, opt = self._setup()
        cfg = adamw.OptConfig(lr=1e-2, warmup_steps=1, decay_steps=100, weight_decay=0.0)

        def loss_fn(p):
            return jnp.sum(p["w"].astype(jnp.float32) ** 2) + jnp.sum(
                p["b"].astype(jnp.float32) ** 2
            )

        l0 = float(loss_fn(params))
        for _ in range(20):
            grads = jax.grad(lambda p: loss_fn(p))(params)
            params, opt, m = adamw.apply(cfg, params, grads, opt)
        assert float(loss_fn(params)) < l0 * 0.8

    def test_masters_stay_fp32(self):
        params, opt = self._setup()
        cfg = adamw.OptConfig()
        grads = jax.tree.map(jnp.ones_like, params)
        params, opt, _ = adamw.apply(cfg, params, grads, opt)
        assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(opt["master"]))
        assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))

    def test_grad_clipping(self):
        params, opt = self._setup()
        cfg = adamw.OptConfig(clip_norm=1.0)
        grads = jax.tree.map(lambda p: jnp.full_like(p, 100.0), params)
        _, _, metrics = adamw.apply(cfg, params, grads, opt)
        assert float(metrics["grad_norm"]) > 1.0  # reported pre-clip


class TestCompression:
    def test_int8_error_feedback_reduces_bias(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
        cfg = CompressionConfig(mode="int8", ef=True)
        residual = None
        total_err = jnp.zeros_like(g)
        acc = jnp.zeros_like(g)
        for _ in range(50):
            payload, residual = compress_grads(cfg, g, residual)
            acc = acc + payload
        # with error feedback the time-averaged payload converges to g
        np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g), atol=2e-3)

    def test_bf16_halves_payload(self):
        from repro.optim.compression import wire_bytes_factor

        assert wire_bytes_factor(CompressionConfig(mode="bf16")) == 0.5
        assert wire_bytes_factor(CompressionConfig(mode="int8")) == 0.25


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {
            "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.bfloat16)},
        }
        mgr.save(7, tree, blocking=True)
        assert mgr.latest_step() == 7
        like = jax.tree.map(jnp.zeros_like, tree)
        out = mgr.restore(7, like)
        np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
        np.testing.assert_array_equal(
            np.asarray(out["nested"]["b"], np.float32),
            np.asarray(tree["nested"]["b"], np.float32),
        )

    def test_gc_keeps_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"x": jnp.ones((2,))}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree, blocking=True)
        assert mgr.steps() == [3, 4]

    def test_elastic_plan_validates(self):
        p = ElasticPlan(old_dp=16, new_dp=8, old_global_batch=256)
        assert p.new_global_batch == 256
        with pytest.raises(ValueError):
            ElasticPlan(old_dp=16, new_dp=7, old_global_batch=256).new_global_batch


class TestDataPipeline:
    def test_deterministic_per_step(self):
        cfg = DataConfig(global_batch=4, seq_len=16, vocab_size=97)
        s1 = SyntheticSource(cfg)
        s2 = SyntheticSource(cfg)
        np.testing.assert_array_equal(s1.batch_at(5), s2.batch_at(5))
        assert not np.array_equal(s1.batch_at(5), s1.batch_at(6))

    def test_next_token_alignment(self):
        cfg = DataConfig(global_batch=2, seq_len=8, vocab_size=64)
        pipe = Pipeline(SyntheticSource(cfg), cfg)
        b = next(pipe)
        # arith pattern: labels are tokens shifted by one position
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
        pipe.close()

    def test_host_sharding_disjoint(self):
        cfg = DataConfig(global_batch=8, seq_len=8, vocab_size=1 << 20, pattern="uniform")
        b0 = SyntheticSource(cfg, host_index=0, host_count=2).batch_at(0)
        b1 = SyntheticSource(cfg, host_index=1, host_count=2).batch_at(0)
        assert b0.shape == (4, 9)
        assert not np.array_equal(b0, b1)

    def test_resume_state(self):
        cfg = DataConfig(global_batch=2, seq_len=8, vocab_size=64)
        pipe = Pipeline(SyntheticSource(cfg), cfg, start_step=0)
        first = next(pipe)
        pipe.close()
        pipe2 = Pipeline(SyntheticSource(cfg), cfg, start_step=first["step"] + 1)
        second = next(pipe2)
        assert second["step"] == first["step"] + 1
        pipe2.close()


class TestFaultTolerance:
    def test_backup_activation(self):
        fo = RackFailover()
        rec = fo.fail(3)
        assert rec["backup_physical"] == 64
        assert fo.translate(3) == 64
        assert not fo.degraded

    def test_no_spare_returns_structured_exhaustion(self):
        fo = RackFailover(n_backups=1)
        fo.fail(1)
        rec = fo.fail(2)
        assert rec["kind"] == "spares_exhausted"
        assert rec["failed_count"] == 2
        assert fo.degraded

    def test_supervisor_detects_dead(self):
        sup = TrainingSupervisor(n_workers=4, heartbeat_timeout_s=1000.0)
        assert sup.dead_workers() == []
        sup.workers[2].last_heartbeat -= 10_000
        assert sup.dead_workers() == [2]

    def test_supervisor_straggler_detection(self):
        sup = TrainingSupervisor(n_workers=2, straggler_factor=2.0)
        for i in range(20):
            sup.heartbeat(0, i, 1.0)
        for i in range(3):
            sup.heartbeat(1, 20 + i, 10.0)
        assert any(e["kind"] == "straggler" for e in sup.events)

    def test_recovery_plan_mixes_backup_and_elastic(self):
        sup = TrainingSupervisor(n_workers=4)
        fo = RackFailover(n_backups=1)
        plan = sup.plan_recovery(fo, [0, 1])
        kinds = [a["kind"] for a in plan["actions"]]
        assert kinds == ["backup", "elastic_shrink"]
        assert plan["restart_from_checkpoint"]
