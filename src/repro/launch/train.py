"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --no-smoke --layers 4 --steps 5 --batch 8 --seq 2048   # one v5e chip
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --no-smoke --layers 8 --steps 5 --batch 8 --seq 2048 --mesh 2x2  # v5e host

``train()`` is the loop on a ("data", "model") mesh, 1x1 by default, called
in-process by ``chip_smoke.py``; ``main()`` is its command line.
``setup_train`` builds the step for the mesh (``train_step.build_train_step``
under the topology-aware rules, ZeRO-1 optimizer state) and makes the state
from the seed already placed; the benchmark's sharded runner and
``chip_smoke.py --four-chips`` call it too.  Features: AdamW,
checkpoint/restart (resumes from the latest step automatically),
fault-tolerant supervision hooks, gradient compression, --auto-parallel
(plans via the §5.2 topology-aware planner and logs the chosen spec).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import load
from repro.data.pipeline import DataConfig, Pipeline, SyntheticSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models.api import ShapeCell
from repro.models.param import param_count, tree_init
from repro.optim import adamw
from repro.optim.compression import CompressionConfig
from repro.runtime.fault_tolerance import TrainingSupervisor
from repro.train.train_step import build_train_step, lower_bundle


@dataclass
class TrainSetup:
    """A train step compiled for a mesh and the state it takes, placed."""

    step: Any             # compiled (params, opt_state, batch) -> (params, opt_state, metrics)
    params: Any
    opt_state: Any
    shardings: tuple      # (params, opt_state, batch) trees of NamedSharding
    compile_s: float

    def feed(self, batch: dict) -> dict:
        """A host batch on the devices, split as the step takes it."""
        return jax.device_put({"tokens": batch["tokens"], "labels": batch["labels"]},
                              self.shardings[2])


def setup_train(harness, mesh, *, batch: int, seq: int, opt_cfg: adamw.OptConfig,
                compression: CompressionConfig, seed: int) -> TrainSetup:
    """Compile one train step of ``harness`` for global batch ``batch`` x
    ``seq`` on ``mesh`` and make its state from ``seed``: parameters and
    optimizer state are made on the devices in the shardings the step
    takes, never whole on one of them.  The compiled module is
    ``jit_train_step``."""
    bundle = build_train_step(harness, ShapeCell("train", "train", seq, batch), mesh,
                              opt_cfg=opt_cfg, compression=compression)
    param_sh, opt_sh, _ = bundle.in_shardings
    specs = harness.param_specs()
    params = jax.jit(lambda key: tree_init(specs, key, dtype=jnp.bfloat16),
                     out_shardings=param_sh)(jax.random.PRNGKey(seed))
    opt_state = jax.jit(adamw.init_opt_state, out_shardings=opt_sh)(params)
    t0 = time.perf_counter()
    step = lower_bundle(bundle, mesh).compile()
    return TrainSetup(step, params, opt_state, bundle.in_shardings,
                      time.perf_counter() - t0)


def parse_mesh(text: str) -> tuple[int, int]:
    """``"2x2"`` -> (data, model) = (2, 2)."""
    data, sep, model = text.partition("x")
    if not sep or not data.isdigit() or not model.isdigit():
        raise argparse.ArgumentTypeError(f"mesh {text!r} is not DATAxMODEL, e.g. 2x2")
    return int(data), int(model)


def train(harness, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
          seed: int = 0, ckpt_dir: str | None = None, ckpt_every: int = 50,
          compression: str = "none", log_every: int = 10, mesh=None) -> dict:
    """Train ``harness`` on the synthetic stream on ``mesh`` (a ("data",
    "model") mesh; one device when None).

    The step is compiled ahead of the first call; each step's clock stops
    after ``block_until_ready``.  Returns ``losses``, ``grad_norms`` and
    ``step_s`` for the steps run, ``param_norm`` after the last,
    ``compile_s``, ``tokens_per_s`` over them and ``start_step`` (non-zero
    when resumed from ``ckpt_dir``).
    """
    cfg = harness.cfg
    mesh = make_smoke_mesh(1, 1) if mesh is None else mesh
    opt_cfg = adamw.OptConfig(lr=lr, warmup_steps=10, decay_steps=steps)
    setup = setup_train(harness, mesh, batch=batch, seq=seq, opt_cfg=opt_cfg,
                        compression=CompressionConfig(mode=compression), seed=seed)
    params, opt_state, train_step = setup.params, setup.opt_state, setup.step

    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if manager and manager.latest_step() is not None:
        s = manager.latest_step()
        print(f"[train] resuming from checkpoint step {s}")
        state = manager.restore(s, {"params": params, "opt": opt_state},
                                {"params": setup.shardings[0], "opt": setup.shardings[1]})
        params, opt_state = state["params"], state["opt"]
        start_step = s

    data_cfg = DataConfig(
        global_batch=batch, seq_len=seq, vocab_size=cfg.vocab_size, seed=seed,
    )
    pipeline = Pipeline(SyntheticSource(data_cfg), data_cfg, start_step=start_step)
    supervisor = TrainingSupervisor(n_workers=jax.device_count())
    losses, grad_norms, step_s = [], [], []
    try:
        for step in range(start_step, steps):
            b = setup.feed(next(pipeline))
            t0 = time.perf_counter()
            params, opt_state, metrics = jax.block_until_ready(
                train_step(params, opt_state, b))
            dt = time.perf_counter() - t0
            supervisor.heartbeat(0, step, dt)
            losses.append(float(metrics["loss"]))
            grad_norms.append(float(metrics["grad_norm"]))
            step_s.append(dt)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step={step} loss={losses[-1]:.4f} "
                      f"gnorm={grad_norms[-1]:.3f} "
                      f"lr={float(metrics['lr']):.2e} dt={dt*1e3:.0f}ms")
            if manager and step > 0 and step % ckpt_every == 0:
                manager.save(step, {"params": params, "opt": opt_state})
        if manager:
            manager.save(steps, {"params": params, "opt": opt_state}, blocking=True)
    finally:
        pipeline.close()
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "step_s": step_s,
        "param_norm": float(jax.jit(adamw.global_norm)(params)),
        "compile_s": setup.compile_s,
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
    ap.add_argument("--mesh", type=parse_mesh, default=(1, 1),
                    help="DATAxMODEL devices, e.g. 2x2 for the four chips of a v5e host")
    args = ap.parse_args()

    enable_compile_cache()
    harness = load(args.arch, smoke=args.smoke)
    if args.layers is not None:
        harness = harness.clone(n_layers=args.layers)
    cfg = harness.cfg
    n_params = param_count(harness.param_specs())
    print(f"[train] arch={args.arch} smoke={args.smoke} "
          f"layers={cfg.n_layers} params={n_params:.3g} mesh={args.mesh}")

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
        mesh=make_smoke_mesh(*args.mesh),
    )
    losses = out["losses"]
    print(f"[train] done. first loss={losses[0]:.4f} last loss={losses[-1]:.4f} "
          f"({out['tokens_per_s']:.0f} tok/s, compile {out['compile_s']:.1f}s)")
    assert losses[-1] < losses[0], "loss did not improve"


if __name__ == "__main__":
    main()
