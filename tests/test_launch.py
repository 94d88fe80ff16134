"""The launchers' in-process entry points (``launch.serve.serve``,
``launch.train.train`` on one device and on a 2x2 mesh), the compile-cache
helper and ``chip_smoke.py``'s refusal to run without a TPU, at smoke sizes
on the CPU."""

import argparse
import os
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load
from repro.launch import compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.launch.serve import prefill_reference_logits, serve
from repro.launch.train import parse_mesh, setup_train, train
from repro.models.layers import Runtime
from repro.models.param import tree_abstract, tree_init
from repro.optim import adamw
from repro.optim.compression import CompressionConfig
from repro.train.train_step import make_train_step

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


def test_parse_mesh():
    assert parse_mesh("2x2") == (2, 2) and parse_mesh("1x4") == (1, 4)
    for bad in ("2", "2x", "x2", "2*2", "ax2"):
        with pytest.raises(argparse.ArgumentTypeError):
            parse_mesh(bad)


def _canonical_hlo(text: str) -> list[str]:
    """A compiled module's computations with instruction names numbered in
    order of appearance and metadata, source frames and sharding
    annotations left out: what the module computes, not how it was
    traced."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines) if line.startswith(("%", "ENTRY")))
    names: dict = {}
    out = []
    for line in lines[first:]:
        line = re.sub(r", metadata=\{[^}]*\}|, stack_frame_id=\d+|, sharding=\{replicated\}",
                      "", line)
        line = re.sub(r", frontend_attributes=.*$", "", line)
        out.append(re.sub(r"%[\w.\-]+", lambda m: "%%%d" % names.setdefault(m[0], len(names)),
                          line))
    return out


def test_one_device_step_compiles_as_a_plain_jit():
    """On a 1x1 mesh the launcher's step (the sharded builder with the
    program's sharding rules) computes exactly what the plain one-device
    jit of ``make_train_step`` computes."""
    h = load("granite-3-2b", smoke=True)
    opt_cfg = adamw.OptConfig(warmup_steps=10, decay_steps=5)
    built = setup_train(h, make_smoke_mesh(1, 1), batch=2, seq=32, opt_cfg=opt_cfg,
                        compression=CompressionConfig(), seed=0)
    specs = h.param_specs()
    batch = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    plain = jax.jit(make_train_step(h.loss(Runtime(rules=None)), opt_cfg, CompressionConfig()),
                    donate_argnums=(0, 1)).lower(
        tree_abstract(specs, dtype=jnp.bfloat16), tree_abstract(adamw.opt_state_specs(specs)),
        {"tokens": batch, "labels": batch}).compile()
    assert built.step.as_text().startswith("HloModule jit_train_step,")
    assert _canonical_hlo(built.step.as_text()) == _canonical_hlo(plain.as_text())


TRAIN_2X2_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import numpy as np
    import chip_smoke
    from repro.configs import load
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.train import train

    h = load("granite-8b", smoke=True)
    kw = dict(steps=3, batch=4, seq=64, log_every=100)
    one, four = train(h, **kw), train(h, mesh=make_smoke_mesh(2, 2), **kw)
    np.testing.assert_allclose(four["losses"], one["losses"], rtol=chip_smoke.LOSS_RTOL)
    np.testing.assert_allclose(four["grad_norms"], one["grad_norms"], rtol=chip_smoke.GNORM_RTOL)
    np.testing.assert_allclose(four["param_norm"], one["param_norm"], rtol=chip_smoke.PNORM_RTOL)
    print("TRAIN_2X2_OK")
    """
)


def test_train_on_2x2_matches_one_device():
    """``train()`` on a 2x2 mesh of 4 host devices against the same three
    steps on one, at chip_smoke.py's tolerances (reasons given there)."""
    r = subprocess.run(
        [sys.executable, "-c", TRAIN_2X2_SCRIPT],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TRAIN_2X2_OK" in r.stdout
