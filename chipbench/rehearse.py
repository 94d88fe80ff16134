"""Compile a cell's programs at their real size for a described TPU v5e,
without a chip, and print each program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <name>

It compiles what a run of the cell compiles: the program's step (train)
or prefill and decode (serve), and the reference's programs, at the
shapes the cell's data files give.  A program the chip's compiler would
refuse, or one that does not fit the chip, fails here at no chip time.
Nothing runs, so it gives bytes, not times.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run  # noqa: E402  (puts chipbench and src on the path)
import program  # noqa: E402
from reference import dense_lm  # noqa: E402


def _on(sharding, tree):
    import jax

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
                        tree)


def _report(name, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments={m.argument_size_in_bytes} outputs={m.output_size_in_bytes} "
          f"temp={m.temp_size_in_bytes} aliased={m.alias_size_in_bytes} "
          f"total={total} ({total / 1e9:.2f} GB)", flush=True)


def rehearse(spec: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models.api import ShapeCell
    from repro.models.layers import Runtime
    from repro.models.param import tree_init
    from repro.optim import adamw
    from repro.optim.compression import CompressionConfig
    from repro.train.train_step import make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model, tr = spec["model"], spec["traffic"]
    h = program.harness(model)
    params = _on(one, jax.eval_shape(
        lambda k: tree_init(h.param_specs(), k, dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    weights = _on(one, jax.eval_shape(lambda k: dense_lm.make_weights(model, k),
                                      jax.random.PRNGKey(0)))
    items = dense_lm.arch(model)
    if tr["runner"] == "train":
        B, S = tr["batch"], tr["seq_len"]
        opt = _on(one, jax.eval_shape(adamw.init_opt_state, params))
        batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
                 for k in ("tokens", "labels")}
        step = jax.jit(make_train_step(h.loss(Runtime(rules=None)),
                                       adamw.OptConfig(**tr["optimizer"]), CompressionConfig()),
                       donate_argnums=(0, 1))
        _report("program train_step", step.lower(params, opt, batch).compile())
        rows = spec["checks"].get("block_rows", 1)
        w32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one),
                           weights)
        acc = (jax.ShapeDtypeStruct((), jnp.float32, sharding=one), w32)
        row = jax.ShapeDtypeStruct((rows, S), jnp.int32, sharding=one)
        _report("reference row gradient", dense_lm._accumulate.lower(
            items, dense_lm.F32, w32, acc, row, row).compile())
    else:
        B, P, G = tr["batch"], tr["prompt_len"], tr["gen_len"]
        rt = Runtime(rules=None)
        cache = _on(one, jax.eval_shape(lambda k: tree_init(
            h.serve_state_specs(ShapeCell("serve", "decode", P + G + 8, B)), k),
            jax.random.PRNGKey(0)))
        i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
        _report("program prefill", jax.jit(h.prefill(rt)).lower(
            params, cache, i32((B, P))).compile())
        _report("program decode", jax.jit(h.decode(rt)).lower(
            params, cache, i32((B, 1)), i32(())).compile())
        rows = spec["checks"].get("block_rows", 1)
        _report("reference served logits", dense_lm._tail_logits.lower(
            items, dense_lm.F32, weights, i32((rows, P + G - 1)), G).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    rehearse(run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), args.workload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
