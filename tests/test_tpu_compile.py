"""Compiles for a described TPU v5e (2x2), no chip attached.

The TPU compiler is installed with jax, so the main path's programs can be
compiled for a v5e here at published widths: it refuses what the chip
would refuse (Mosaic tiling, memory), and ``memory_analysis()`` gives the
bytes per device.  Nothing runs, so nothing here is a time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import load
from repro.kernels.flash_attention import flash_attention
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_smoke_mesh
from repro.models.api import ShapeCell
from repro.models.layers import Runtime
from repro.models.param import tree_abstract
from repro.optim import adamw
from repro.optim.compression import CompressionConfig
from repro.train.train_step import build_train_step, lower_bundle, make_train_step

# published widths; batch and sequence cut to keep each compile near 10 s
TRAIN_CELL = ShapeCell("compile_train", "train", 256, 2)
OPT = adamw.OptConfig(warmup_steps=10, decay_steps=100)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def granite():
    return load("granite-3-2b")


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def single_train(one_chip, granite):
    """granite-3-2b's train step at 1 layer, published widths, one chip."""
    h = granite.clone(n_layers=1)
    specs = h.param_specs()
    args = (
        tree_abstract(specs, dtype=jnp.bfloat16),
        tree_abstract(adamw.opt_state_specs(specs)),
        tree_abstract(h.train_input_specs(TRAIN_CELL)),
    )
    step = make_train_step(h.loss(Runtime(rules=None)), OPT, CompressionConfig())
    return jax.jit(step, donate_argnums=(0, 1)).lower(*_on(one_chip, args)).compile()


def test_flash_attention_compiles_at_granite_widths(one_chip):
    # GQA: 32 query heads over 8 kv heads, head_dim 64, S = 4096
    q = jax.ShapeDtypeStruct((1, 8, 4, 4096, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 64), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False))
    txt = fn.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in txt


def test_decode_step_compiles_at_published_widths(one_chip, granite):
    h = granite.clone(n_layers=2)
    cell = ShapeCell("compile_decode", "decode", 2048, 8)
    compiled = jax.jit(h.decode(Runtime(rules=None))).lower(
        *_on(one_chip, (
            tree_abstract(h.param_specs(), dtype=jnp.bfloat16),
            tree_abstract(h.serve_state_specs(cell)),
            jax.ShapeDtypeStruct((8, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        ))
    ).compile()
    assert 0 < _bytes(compiled) < 16e9


def test_train_step_compiles_at_published_widths(single_train):
    assert 0 < _bytes(single_train) < 16e9


def test_sharded_train_step_spreads_over_2x2(topo, granite, single_train):
    h = granite.clone(n_layers=1)
    mesh = make_smoke_mesh(2, 2, devices=topo.devices)
    compiled = lower_bundle(build_train_step(h, TRAIN_CELL, mesh, opt_cfg=OPT),
                            mesh).compile()
    stats = collective_stats(compiled.as_text())
    assert stats.by_kind.get("all-reduce", 0) > 0     # gradient reduction
    assert stats.by_kind.get("all-gather", 0) > 0     # model-sharded weights
    assert _bytes(compiled) < _bytes(single_train) / 2
