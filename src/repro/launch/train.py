"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --no-smoke --layers 4 --steps 5 --batch 8 --seq 2048   # one v5e chip

``train()`` is the loop on one device (rules=None), called in-process by
``chip_smoke.py``; ``main()`` is its command line.  The sharded step for a
mesh is ``train.train_step.build_train_step``.  Features: AdamW,
checkpoint/restart (resumes from the latest step automatically),
fault-tolerant supervision hooks, gradient compression, --auto-parallel
(plans via the §5.2 topology-aware planner and logs the chosen spec).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import load
from repro.data.pipeline import DataConfig, Pipeline, SyntheticSource
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import Runtime
from repro.models.param import param_count, tree_init
from repro.optim import adamw
from repro.optim.compression import CompressionConfig
from repro.runtime.fault_tolerance import TrainingSupervisor
from repro.train.train_step import make_train_step


def train(harness, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
          seed: int = 0, ckpt_dir: str | None = None, ckpt_every: int = 50,
          compression: str = "none", log_every: int = 10) -> dict:
    """Train ``harness`` on the synthetic stream on the default device.

    The step is compiled ahead of the first call; each step's clock stops
    after ``block_until_ready``.  Returns ``losses`` and ``step_s`` for the
    steps run, ``compile_s``, ``tokens_per_s`` over them and
    ``start_step`` (non-zero when resumed from ``ckpt_dir``).
    """
    cfg = harness.cfg
    loss_fn = harness.loss(Runtime(rules=None))
    opt_cfg = adamw.OptConfig(lr=lr, warmup_steps=10, decay_steps=steps)
    comp = CompressionConfig(mode=compression)

    params = tree_init(harness.param_specs(), jax.random.PRNGKey(seed),
                       dtype=jnp.bfloat16)
    opt_state = adamw.init_opt_state(params)

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if manager and manager.latest_step() is not None:
        s = manager.latest_step()
        print(f"[train] resuming from checkpoint step {s}")
        state = manager.restore(s, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = s

    batch_spec = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    t0 = time.perf_counter()
    train_step = jax.jit(
        make_train_step(loss_fn, opt_cfg, comp), donate_argnums=(0, 1)
    ).lower(params, opt_state, {"tokens": batch_spec, "labels": batch_spec}).compile()
    compile_s = time.perf_counter() - t0

    data_cfg = DataConfig(
        global_batch=batch, seq_len=seq, vocab_size=cfg.vocab_size, seed=seed,
    )
    pipeline = Pipeline(SyntheticSource(data_cfg), data_cfg, start_step=start_step)
    supervisor = TrainingSupervisor(n_workers=jax.device_count())
    losses, step_s = [], []
    try:
        for step in range(start_step, steps):
            b = next(pipeline)
            b = {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}
            t0 = time.perf_counter()
            params, opt_state, metrics = jax.block_until_ready(
                train_step(params, opt_state, b))
            dt = time.perf_counter() - t0
            supervisor.heartbeat(0, step, dt)
            losses.append(float(metrics["loss"]))
            step_s.append(dt)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step={step} loss={losses[-1]:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} dt={dt*1e3:.0f}ms")
            if manager and step > 0 and step % ckpt_every == 0:
                manager.save(step, {"params": params, "opt": opt_state})
        if manager:
            manager.save(steps, {"params": params, "opt": opt_state}, blocking=True)
    finally:
        pipeline.close()
    return {
        "losses": losses,
        "step_s": step_s,
        "compile_s": compile_s,
        "tokens_per_s": len(step_s) * batch * seq / max(sum(step_s), 1e-12),
        "start_step": start_step,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument(
        "--smoke", action=argparse.BooleanOptionalAction, default=True,
        help="shrunken config (default; --no-smoke for the published widths)",
    )
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths unchanged)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compression", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--auto-parallel", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    harness = load(args.arch, smoke=args.smoke)
    if args.layers is not None:
        harness = harness.clone(n_layers=args.layers)
    cfg = harness.cfg
    n_params = param_count(harness.param_specs())
    print(f"[train] arch={args.arch} smoke={args.smoke} "
          f"layers={cfg.n_layers} params={n_params:.3g}")

    if args.auto_parallel:
        from repro.core.cost_model import Routing, build_comm_model
        from repro.core.planner import plan
        from repro.core.traffic import WorkloadSpec

        w = WorkloadSpec(
            name=args.arch,
            n_layers=cfg.n_layers,
            hidden=cfg.d_model,
            n_heads=getattr(cfg, "n_heads", cfg.d_model // 64),
            head_dim=getattr(cfg, "head_dim", 64),
            seq_len=args.seq,
            global_batch=max(args.batch, 256),
            params_total=float(n_params),
        )
        comm = build_comm_model(multi_pod=True, routing=Routing.BORROW)
        for r in plan(w, 512, comm, top_k=3):
            s = r.spec
            print(f"[planner] tp={s.tp} sp={s.sp} pp={s.pp} dp={s.dp} ep={s.ep} "
                  f"m={s.microbatches} iter={r.iteration_s:.3f}s")

    out = train(
        harness, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
        seed=args.seed, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        compression=args.compression, log_every=args.log_every,
    )
    losses = out["losses"]
    print(f"[train] done. first loss={losses[0]:.4f} last loss={losses[-1]:.4f} "
          f"({out['tokens_per_s']:.0f} tok/s, compile {out['compile_s']:.1f}s)")
    assert losses[-1] < losses[0], "loss did not improve"


if __name__ == "__main__":
    main()
