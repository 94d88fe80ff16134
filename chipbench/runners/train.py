"""Training cells: the program's one-device train step in a timed loop.

Set-up builds one compiled step with its state, the way
``launch.train.train`` does (``make_train_step`` over the harness loss,
jitted with the parameters and optimizer state donated), feeds it from
the program's ``Pipeline`` over the benchmark's seeded source, and runs
its first steps through the same call and feed as the window.  Those
steps are what the comparison reads: the loss of each, each tensor's
clipped gradient as the optimizer got it (its first moment after one
step, over 1 - b1) and each tensor's change after the last of them,
against a copy of the initial weights kept on the device (``tree_init``
inlined into the program that takes the norms rounded some bfloat16 draws
otherwise on the chip, which read as a change 5-9% too large).  The
window then runs the same object on, step after step, with
``ahead_steps`` steps dispatched beyond the one whose loss is read, so
that a stall of the host does not leave the chip idle; when its time is
up it sends no more steps, reads every loss that was sent and closes.
After the window the program's state is freed and the float32 reference
follows the same first steps.
"""

from __future__ import annotations

import collections
import gc

import numpy as np

import compare
import program
import traffic as traffic_gen
from reference import dense_lm


def _leaf_names(tree) -> list[str]:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]


def run(cell) -> "compare.Outcome":
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, Pipeline
    from repro.models.layers import Runtime
    from repro.models.param import tree_init
    from repro.optim import adamw
    from repro.optim.compression import CompressionConfig
    from repro.train.train_step import make_train_step

    model, tr = cell.model, cell.traffic
    B, S, V = tr["batch"], tr["seq_len"], model["vocab_size"]
    opt = tr["optimizer"]
    setup_steps = tr["setup_steps"]
    h = program.harness(model)
    opt_cfg = adamw.OptConfig(**opt)

    params = tree_init(h.param_specs(), cell.key, dtype=jnp.bfloat16)
    start = jax.tree.map(jnp.copy, params)
    state = {"params": params, "opt": jax.jit(adamw.init_opt_state)(params)}
    del params
    spec = jax.ShapeDtypeStruct((B, S), jnp.int32)
    step = jax.jit(
        program.named(make_train_step(h.loss(Runtime(rules=None)), opt_cfg,
                                      CompressionConfig()), "train_step"),
        donate_argnums=(0, 1),
    ).lower(state["params"], state["opt"], {"tokens": spec, "labels": spec}).compile()

    leaf_norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)])
    change_norms = jax.jit(lambda master, start: [
        jnp.sqrt(jnp.sum(jnp.square(m - p.astype(jnp.float32))))
        for m, p in zip(jax.tree.leaves(master), jax.tree.leaves(start))])
    names = _leaf_names(state["params"])

    source = traffic_gen.SyntheticSource(B, S, V, cell.seed32)
    pipeline = Pipeline(source, DataConfig(global_batch=B, seq_len=S, vocab_size=V,
                                           seed=cell.seed32))
    losses: list[float] = []
    pending = collections.deque()   # losses of steps sent and not yet read
    ahead = tr["ahead_steps"]

    def send():
        with cell.span("data"):
            b = next(pipeline)
            b = {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}
        with cell.span("dispatch"):
            state["params"], state["opt"], metrics = step(state["params"], state["opt"], b)
            metrics["loss"].copy_to_host_async()
        pending.append(metrics["loss"])

    def read():
        with cell.span("sync"):
            losses.append(float(pending.popleft()))

    def one_step():
        send()
        read()

    try:
        one_step()
        grad = [float(x) / (1 - opt["b1"]) for x in leaf_norms(state["opt"]["m"])]
        for _ in range(setup_steps - 1):
            one_step()
        change = [float(x) for x in change_norms(state["opt"]["master"], start)]
        del start
        got = {"losses": losses[:setup_steps], "grad_norms": dict(zip(names, grad)),
               "change_norms": dict(zip(names, change))}
        with cell.window() as clock:
            while clock.elapsed() < cell.seconds:
                send()
                while len(pending) > ahead:
                    read()
            while pending:
                read()
        window_s = clock.seconds
    finally:
        pipeline.close()

    steps = len(losses) - setup_steps
    attempted = len(losses)
    failed = int(np.sum(~np.isfinite(losses)))
    memory = cell.memory_peak_bytes()
    state.clear()
    del step
    gc.collect()

    batches = [source.batch_at(i) for i in range(setup_steps)]
    block = cell.checks.get("block_rows", 1)
    feed = [(b[:, :-1], b[:, 1:]) for b in batches]
    want = dense_lm.train_readings(model, opt, cell.key, feed, block_rows=block)
    numbers = compare.train_numbers(got, want)
    detail = {"program": compare.train_detail(got, want)}
    variants = {}
    if "fp8" in cell.variants:
        ctrl = dense_lm.train_readings(model, opt, cell.key, feed, dense_lm.FP8, block)
        variants["fp8"] = compare.train_numbers(ctrl, want)
        detail["fp8"] = compare.train_detail(ctrl, want)
    if "half_batch" in cell.variants:
        half = [(t[: B // 2], lab[: B // 2]) for t, lab in feed]
        fault = dense_lm.train_readings(model, opt, cell.key, half, block_rows=block)
        variants["half_batch"] = compare.train_numbers(fault, want)
        detail["half_batch"] = compare.train_detail(fault, want)
    return compare.Outcome(
        metrics={"train_tokens_per_s": steps * B * S / window_s},
        attempted=attempted, failed=failed, memory_peak_bytes=memory,
        numbers=numbers, variants=variants, detail=detail,
        counts={"train_steps": steps, "window_s": window_s, "batch": B, "seq_len": S,
                "ahead_steps": ahead},
    )
