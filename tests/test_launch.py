"""The launchers' in-process entry points (``launch.serve.serve``,
``launch.train.train``), the compile-cache helper and ``chip_smoke.py``'s
refusal to run without a TPU, at smoke sizes on the CPU."""

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load
from repro.launch import compile_cache
from repro.launch.serve import prefill_reference_logits, serve
from repro.launch.train import train
from repro.models.param import tree_init

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_serve_first_decode_matches_prefill():
    h = load("granite-3-2b", smoke=True)
    vocab = h.cfg.vocab_size
    params = tree_init(h.param_specs(), jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (2, 16)), jnp.int32)
    out = serve(h, params, prompts, gen=3)
    tokens = out["tokens"]
    assert tokens.shape == (2, 3)
    assert np.all((tokens >= 0) & (tokens < vocab))
    assert set(out["timings"]) == {
        "prefill_compile_s", "decode_compile_s", "prefill_s", "decode_s_per_token"}
    ref = prefill_reference_logits(h, params, prompts, jnp.asarray(tokens[:, 0]),
                                   out["max_len"])
    dec = np.asarray(out["decode_logits"][:, -1, :vocab], np.float32)
    ref = np.asarray(ref[:, -1, :vocab], np.float32)
    assert np.isfinite(dec).all()
    assert np.sqrt(np.mean((dec - ref) ** 2) / np.mean(ref ** 2)) < 1e-2
    # the greedy token after the prompt is the prefill logits' argmax
    np.testing.assert_array_equal(
        tokens[:, 0], np.asarray(out["prefill_logits"][:, -1, :vocab]).argmax(-1))


def test_train_runs_the_requested_steps():
    h = load("granite-3-2b", smoke=True)
    out = train(h, steps=3, batch=2, seq=32, log_every=100)
    assert len(out["losses"]) == len(out["step_s"]) == 3
    assert np.isfinite(out["losses"]).all()
    assert out["compile_s"] > 0 and out["tokens_per_s"] > 0
    assert out["start_step"] == 0


def test_train_resumes_from_checkpoint(tmp_path):
    h = load("granite-3-2b", smoke=True)
    train(h, steps=2, batch=2, seq=32, ckpt_dir=str(tmp_path), log_every=100)
    out = train(h, steps=3, batch=2, seq=32, ckpt_dir=str(tmp_path), log_every=100)
    assert out["start_step"] == 2 and len(out["losses"]) == 1


def test_compile_cache_env_wins(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.enable_compile_cache() == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_without_tpu():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


FOUR_CHIP_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import jax
    import chip_smoke
    from repro.configs import load

    chip_smoke.four_chip_phase(load("granite-3-2b", smoke=True), jax.devices(),
                               batch=4, seq=64, seed=0)
    print("FOUR_CHIP_OK")
    """
)


def test_sharded_train_step_matches_one_device():
    """chip_smoke.py's --four-chips phase on 4 host devices."""
    r = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_SCRIPT],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR_CHIP_OK" in r.stdout
