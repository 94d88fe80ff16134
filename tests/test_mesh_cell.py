"""The benchmark's four-chip training cell (``granite-8b.train.2x2-b8s2k``)
at smoke widths on four host devices, each case in a subprocess that asks
for them: the float32 reference with its state split over the devices
equals the one-device reference, and the cell's runner, which drives the
launcher's sharded step, is judged correct while the float8 control and
half a batch are not, nor a step that leaves out the gradients'
reduction over the data axis."""

import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]

HEAD = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = ["chipbench", "chipbench/tests"]
    import pathlib, tempfile
    import conftest, run, compare, traffic
    import jax, numpy as np

    # the benchmark's compile cache lives in the checkout; keep tests out of it
    run.CACHE_DIR = pathlib.Path(tempfile.mkdtemp(prefix="mesh-cell-cache-"))

    CELL = "granite-8b.train.2x2-b8s2k"
    spec = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), CELL)
    spec["model"] = dict(spec["model"], **conftest.SMOKE_MODEL)
    spec["traffic"] = dict(spec["traffic"], **conftest.SMOKE_TRAFFIC["train"])
    spec["checks"] = dict(spec["checks"], limits=conftest.SMOKE_LIMITS["train"])
    assert len(jax.devices()) == 4
    """
)

REFERENCE = HEAD + textwrap.dedent(
    """
    from reference import dense_lm, sharded_lm

    model, opt = spec["model"], spec["traffic"]["optimizer"]
    source = traffic.SyntheticSource(4, 32, model["vocab_size"], 7)
    feed = [(b[:, :-1], b[:, 1:]) for b in (source.batch_at(i) for i in range(3))]
    key = jax.random.PRNGKey(7)
    want = dense_lm.train_readings(model, opt, key, feed, block_rows=2)
    got = sharded_lm.train_readings(model, opt, key, feed, jax.devices(), block_rows=2)
    # float32 rounding; the change is a difference of weights some 50
    # times its size, so their rounding shows in it that much larger
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for kind, rtol in (("grad_norms", 1e-5), ("change_norms", 3e-5)):
        assert set(got[kind]) == set(want[kind])
        for k, w in want[kind].items():
            np.testing.assert_allclose(got[kind][k], w, rtol=rtol, err_msg=k)

    # with both halves of each batch alike, leaving out the reduction over
    # them changes nothing; with the real batches it does
    twin = [(np.concatenate([t[:2], t[:2]]), np.concatenate([l[:2], l[:2]])) for t, l in feed]
    whole = sharded_lm.train_readings(model, opt, key, twin, jax.devices(), block_rows=2)
    local = sharded_lm.train_readings(model, opt, key, twin, jax.devices(), block_rows=2,
                                      halves=True)
    for kind in ("grad_norms", "change_norms"):
        for k, w in whole[kind].items():
            np.testing.assert_allclose(local[kind][k], w, rtol=3e-5, err_msg=k)
    apart = sharded_lm.train_readings(model, opt, key, feed, jax.devices(), block_rows=2,
                                      halves=True)
    assert max(abs(apart["grad_norms"][k] / w - 1) for k, w in want["grad_norms"].items()) > 1e-3

    placed = sharded_lm.shardings(model, jax.devices())
    weights = sharded_lm._make_weights(dense_lm.arch(model), placed, key)
    made = dense_lm.make_weights(model, key)
    for name, sharding in placed:
        assert weights[name].sharding == sharding, name
        assert len(weights[name].sharding.device_set) == 4, name
        np.testing.assert_array_equal(np.asarray(weights[name]), np.asarray(made[name]))
    print("REFERENCE_OK")
    import shutil; shutil.rmtree(run.CACHE_DIR)
    """
)

RUNNER = HEAD + textwrap.dedent(
    """
    code, line, outcome = run.run_cell(spec, 2**31 + 99, 0.3, False, require_tpu=False,
                                       variants=("fp8", "half_batch", "dropped_reduce"))
    assert code == 0 and line["correct"], line["checks"]
    assert line["attempted"] > 3 and line["failed"] == 0
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    for variant in ("fp8", "half_batch", "dropped_reduce"):
        ok, _ = compare.judge(outcome.variants[variant], spec["checks"]["limits"])
        assert not ok, (variant, outcome.variants[variant])
    counts = outcome.counts
    assert counts["mesh"] == {"data": 2, "model": 2}
    kinds = counts["collectives"]["jit_train_step"]
    assert kinds["all-gather"]["count"] > 0 and kinds["all-reduce"]["count"] > 0
    assert all(k["wire_bytes"] > 0 for k in kinds.values())
    mesh_runner = run.load_module(run.HERE / "runners" / "train_mesh.py")
    assert mesh_runner.harness(spec["model"]).cfg.rms_eps == spec["model"]["rms_norm_eps"] == 1e-5
    print("VARIANTS", outcome.variants)
    print("RUNNER_OK")
    import shutil; shutil.rmtree(run.CACHE_DIR)
    """
)


def _run(script: str, marker: str) -> None:
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert marker in r.stdout


def test_sharded_reference_equals_dense_lm():
    _run(REFERENCE, "REFERENCE_OK")


def test_mesh_cell_runner_judges_program_control_and_fault():
    _run(RUNNER, "RUNNER_OK")
