"""Training cells on a ("data", "model") mesh of the cell's chips: the
program's sharded train step in a timed loop.

Set-up builds the step and its state through the program's launcher,
``launch.train.setup_train``, for the mesh the traffic file names, as
``launch.train.train`` does: the step of ``train_step.build_train_step``
under the program's sharding rules (ZeRO-1 optimizer state over ``data``,
sequence-parallel activations over ``model``), compiled as
``jit_train_step``, with parameters and optimizer state made from the
seed on the chips that hold them.  Each global batch goes onto the chips
in the step's own input sharding.  The first steps run through the same
call and feed as the window and are what the comparison reads, as in
``runners/train.py``: the loss of each, each tensor's clipped gradient
as the optimizer got it (its first moment after one step, over 1 - b1)
and each tensor's change after the last of them, against a copy of the
initial weights kept on the chips; every norm is taken on the sharded
arrays.  The window runs the step on with ``ahead_steps`` steps
dispatched beyond the one whose loss is read.  The collectives the
compiler put into the step are counted from its HLO
(``launch.hlo_stats.collective_stats``).  After the window the program's
state is freed and the float32 reference, its own state split over the
same chips (``reference/sharded_lm.py``), follows the same first steps.

Variants for ``calibrate.py``: ``fp8`` and ``half_batch`` as in
``runners/train.py``, and ``dropped_reduce``, the fault of a step that
leaves out the gradients' reduction over ``data``: each data half's
ZeRO-1 share of the state updated from its own half batch.
"""

from __future__ import annotations

import collections
import gc

import numpy as np

import compare
import program
import traffic as traffic_gen
from reference import sharded_lm

from repro.launch.train import setup_train  # noqa: E402  (src is on the path from program)


def _leaf_names(tree) -> list[str]:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", k)) for k in path) for path, _ in paths]


def collective_counts(hlo_text: str) -> dict:
    """Collective ops of a compiled module by kind: how many, and their
    wire bytes per device (``collective_stats``' ring conventions)."""
    from repro.launch.hlo_stats import collective_stats

    stats = collective_stats(hlo_text)
    out: dict = {}
    for kind, _, _ in stats.ops:
        out.setdefault(kind, {"count": 0, "wire_bytes": stats.by_kind[kind]})["count"] += 1
    return dict(sorted(out.items()))


def harness(model: dict):
    """The program's harness for ``model``, with the RMSNorm epsilon that
    the configuration file states (``program.harness`` leaves it at the
    program's own)."""
    return program.harness(model).clone(rms_eps=model["rms_norm_eps"])


def run(cell) -> "compare.Outcome":
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, Pipeline
    from repro.launch.mesh import make_smoke_mesh
    from repro.optim import adamw
    from repro.optim.compression import CompressionConfig

    model, tr = cell.model, cell.traffic
    B, S, V = tr["batch"], tr["seq_len"], model["vocab_size"]
    opt = tr["optimizer"]
    setup_steps = tr["setup_steps"]
    mesh = make_smoke_mesh(tr["mesh"]["data"], tr["mesh"]["model"], devices=cell.devices)
    built = setup_train(harness(model), mesh, batch=B, seq=S,
                        opt_cfg=adamw.OptConfig(**opt), compression=CompressionConfig(),
                        seed=cell.seed32)
    step = built.step
    state = {"params": built.params, "opt": built.opt_state}
    # the initial weights, split as the float32 master is, so that the
    # copy costs a chip a quarter of the bfloat16 weights
    start = jax.jit(lambda p: jax.tree.map(jnp.copy, p),
                    out_shardings=built.shardings[1]["master"])(built.params)
    collectives = {"jit_train_step": collective_counts(step.as_text())}

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
            b = built.feed(next(pipeline))
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
    del step, built
    gc.collect()

    batches = [source.batch_at(i) for i in range(setup_steps)]
    block = cell.checks.get("block_rows", 1)
    feed = [(b[:, :-1], b[:, 1:]) for b in batches]

    def reference(rows, precision=sharded_lm.dense_lm.F32, halves=False):
        return sharded_lm.train_readings(model, opt, cell.key, rows, cell.devices,
                                         precision, block, halves)

    want = reference(feed)
    numbers = compare.train_numbers(got, want)
    detail = {"program": compare.train_detail(got, want)}
    variants = {}
    if "fp8" in cell.variants:
        ctrl = reference(feed, sharded_lm.dense_lm.FP8)
        variants["fp8"] = compare.train_numbers(ctrl, want)
        detail["fp8"] = compare.train_detail(ctrl, want)
    if "half_batch" in cell.variants:
        fault = reference([(t[: B // 2], lab[: B // 2]) for t, lab in feed])
        variants["half_batch"] = compare.train_numbers(fault, want)
        detail["half_batch"] = compare.train_detail(fault, want)
    if "dropped_reduce" in cell.variants:
        fault = reference(feed, halves=True)
        variants["dropped_reduce"] = compare.train_numbers(fault, want)
        detail["dropped_reduce"] = compare.train_detail(fault, want)
    return compare.Outcome(
        metrics={"train_tokens_per_s": steps * B * S / window_s},
        attempted=attempted, failed=failed, memory_peak_bytes=memory,
        numbers=numbers, variants=variants, detail=detail,
        counts={"train_steps": steps, "window_s": window_s, "batch": B, "seq_len": S,
                "ahead_steps": ahead, "mesh": dict(mesh.shape), "collectives": collectives},
    )
