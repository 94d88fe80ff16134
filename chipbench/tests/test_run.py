"""Whole runs of each cell at smoke size on the CPU: a sound program is
judged correct, and the lower-precision control and each planted fault
are judged not correct under the cell's own limits.  Also: without a TPU
the benchmark prints no result and exits non-zero."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import compare
import run
from conftest import smoke

TRAIN = "granite-3-2b.train.b8s2k"
SERVE = ("granite-8b.serve.decode-b16", "granite-3-2b.serve.prefill-2k")
SEED = 2**31 + 99


def _run(workload, variants=()):
    code, line, _ = run.run_cell(smoke(workload), SEED, 0.3, False, require_tpu=False,
                                 variants=variants)
    assert code == 0
    json.dumps(run.finite(line))
    return line


def test_no_tpu_exits_nonzero_with_no_result():
    p = subprocess.run(
        [sys.executable, str(run.HERE / "run.py"), "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", (TRAIN,) + SERVE)
def test_sound_program_is_correct(workload):
    line = _run(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", (TRAIN,) + SERVE)
def test_lower_precision_control_is_not_correct(workload):
    """The control is the reference computed in float8, in the program's
    place."""
    spec = smoke(workload)
    _, line, outcome = run.run_cell(spec, SEED, 0.3, False, require_tpu=False,
                                    variants=("fp8",))
    assert line["correct"]
    ok, _ = compare.judge(outcome.variants["fp8"], spec["checks"]["limits"])
    assert not ok


def _break_train_step(monkeypatch, fault):
    import repro.train.train_step as ts

    real = ts.make_train_step

    def broken(loss_fn, opt_cfg, compression):
        step = real(loss_fn, opt_cfg, compression)

        def faulty(params, opt_state, batch):
            if fault == "unchanged":
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt_state, half)

        return faulty

    monkeypatch.setattr(ts, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(fault, monkeypatch):
    _break_train_step(monkeypatch, fault)
    line = _run(TRAIN)
    assert not line["correct"], line["checks"]


def _break_decode(monkeypatch, fault):
    from repro.models.api import TransformerHarness

    real = TransformerHarness.decode

    def broken(self, rt):
        fn = real(self, rt)

        def faulty(params, cache, tokens, pos):
            logits, new_cache = fn(params, cache, tokens, pos)
            if fault == "token":       # every answer moved to the next id
                return jnp.roll(logits, 1, axis=-1), new_cache
            return logits, cache       # the cache left as it was

        return faulty

    monkeypatch.setattr(TransformerHarness, "decode", broken)


@pytest.mark.parametrize("fault", ["token", "unchanged"])
@pytest.mark.parametrize("workload", SERVE)
def test_serve_fault_is_not_correct(workload, fault, monkeypatch):
    _break_decode(monkeypatch, fault)
    line = _run(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", (TRAIN,) + SERVE)
def test_traced_run_reports_the_cells_per_layer_metrics(workload, monkeypatch):
    """A ``--trace 1`` run: every per-layer metric the cell lists is read,
    and the line carries busy and window seconds and the breakdown.  The
    CPU has no device plane, so the reduced trace is given."""
    import devtrace

    reduced = devtrace.Reduced(
        window_s=1.0, busy_s=0.9, devices=1,
        modules={"jit_prefill": [2, 0.5], "jit_decode": [14, 0.4], "jit_train_step": [2, 0.9]},
        ops={"fusion.1": 0.6, "convolution.2": 0.3}, idle_by_span={"sync": 0.1},
        idle_gaps=3, collective_s=0.0, collective_exposed_s=0.0,
        starts={"jit_decode": [0.1, 0.13, 0.16], "jit_prefill": [0.05]})
    monkeypatch.setattr(devtrace, "reduce_dir", lambda path: reduced)
    monkeypatch.setattr(run, "device_peaks", lambda kind: run.load_json(
        run.HERE / "peaks.json")["devices"]["TPU v5 lite"])
    spec = smoke(workload)
    code, line, _ = run.run_cell(spec, SEED, 0.3, True, require_tpu=False)
    assert code == 0 and line["correct"]
    assert set(line["metrics"]) == {m["name"] for m in spec["per_layer"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["busy_s"] == 0.9 and line["device"]["window_s"] == 1.0
    assert line["breakdown"]["idle_gaps"] == [["sync", 0.1]]
    assert list(line)[-1] == "checks"
