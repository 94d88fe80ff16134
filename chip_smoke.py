"""Smoke run of the model stack on a TPU, through the normal launchers.

    python3 chip_smoke.py                # one chip: serve + train granite-3-2b
    python3 chip_smoke.py --four-chips   # 2x2 sharded train step vs one device

Default phases, in one process on one chip:

* serve: granite-3-2b at its published config (40 layers), bf16 random
  weights from ``--seed``, through ``launch.serve.serve``: batch 8, a
  1024-token prompt, 32 greedy tokens.  Logits must be finite, token ids in
  range, and the first decode step must agree with a prefill over the
  prompt plus the token it was fed.
* train: the same widths cut to 4 layers, 5 AdamW steps at 8 x 2048
  through ``launch.train.train``; every loss must be finite.

``--four-chips`` runs one train step of the 4-layer model on a 2x2
("data", "model") mesh and the same step on one device, both built by
``launch.train.setup_train``, and compares them.

Times and memory printed here are smoke numbers, not benchmark numbers.
Any failed check exits non-zero; the last line of stdout is the JSON
result, printed only when every phase passed.  Without a TPU the script
exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "granite-3-2b"
# depth 40 -> 4 for training: bf16 params + fp32 master, m and v take
# 14 bytes per parameter, ~37 GB at 40 layers; 4 layers fit a 16 GB v5e
TRAIN_LAYERS = 4
SERVE_BATCH, PROMPT_LEN, GEN = 8, 1024, 32
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 5

# prefill-then-decode vs the full prefill, relative RMS over the logits.
# Both paths run in bf16 (8 mantissa bits, eps ~0.004), and they round at
# different points: one query row against the whole cache vs 1025 rows
# with a causal mask, and the matmuls tile differently.  The gap grows
# about as the square root of depth: on the CPU backend at these widths
# it was 0.8% at 4 layers and 1.4% at 16, so about 2% at 40.  A wrong
# position, mask or cache offset gives errors of order 1.
DECODE_RTOL = 0.05

# sharded (2x2) vs one-device train step.  The loss is a mean over 16k
# tokens, so bf16 partial sums reduced in another order move it far less
# than 0.5%.  The gradient norm is the sensitive check: a missing or doubled
# data-parallel reduction changes it by a factor of 2**0.5 or more, while
# bf16 reduction order moves it by well under 5%.  One AdamW step moves
# the params by lr/warmup per element, so their norm must agree to 0.1%.
LOSS_RTOL, GNORM_RTOL, PNORM_RTOL = 5e-3, 5e-2, 1e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def memory_line(device) -> str:
    stats = device.memory_stats()
    if not stats:
        return "memory_stats not reported"
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} (since start)")


def serve_phase(harness, *, batch: int, prompt_len: int, gen: int, seed: int,
                rtol: float) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import prefill_reference_logits, serve
    from repro.models.param import tree_init

    cfg = harness.cfg
    vocab = cfg.vocab_size
    print(f"[smoke] serve {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={vocab} "
          f"batch={batch} prompt={prompt_len} gen={gen}", flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(tree_init(
        harness.param_specs(), jax.random.PRNGKey(seed), dtype=jnp.bfloat16))
    print(f"[smoke] serve init_s={time.perf_counter() - t0:.3f}", flush=True)
    prompts = jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, size=(batch, prompt_len), dtype=np.int32))

    out = serve(harness, params, prompts, gen=gen, seed=seed)
    t = out["timings"]
    print(f"[smoke] serve compile_s prefill={t['prefill_compile_s']:.3f} "
          f"decode={t['decode_compile_s']:.3f}", flush=True)
    print(f"[smoke] serve wall prefill_s={t['prefill_s']:.4f} "
          f"decode_s_per_token={t['decode_s_per_token']:.5f} (smoke numbers)",
          flush=True)

    tokens = out["tokens"]
    check(tokens.shape == (batch, gen), f"token shape {tokens.shape}")
    check(bool(np.all((tokens >= 0) & (tokens < vocab))), "token id out of range")
    pre = np.asarray(out["prefill_logits"][:, -1, :vocab], np.float32)
    dec = np.asarray(out["decode_logits"][:, -1, :vocab], np.float32)
    check(bool(np.isfinite(pre).all()), "prefill logits not finite")
    check(bool(np.isfinite(dec).all()), "decode logits not finite")

    ref = prefill_reference_logits(harness, params, prompts,
                                   jnp.asarray(tokens[:, 0]), out["max_len"])
    ref = np.asarray(ref[:, -1, :vocab], np.float32)
    rel = float(np.sqrt(np.mean((dec - ref) ** 2) / np.mean(ref ** 2)))
    max_abs = float(np.max(np.abs(dec - ref)))
    agree = int(np.sum(dec.argmax(-1) == ref.argmax(-1)))
    print(f"[smoke] serve decode-vs-prefill rel_rms={rel:.3e} (limit {rtol}) "
          f"max_abs={max_abs:.3e} argmax_agree={agree}/{batch}", flush=True)
    check(rel <= rtol, f"decode logits differ from prefill: rel_rms {rel:.3e}")
    print(f"[smoke] serve ok; {memory_line(jax.devices()[0])}", flush=True)


def train_phase(harness, *, steps: int, batch: int, seq: int, seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch.train import train

    cfg = harness.cfg
    print(f"[smoke] train {cfg.name}: layers={cfg.n_layers} (cut from the "
          f"published depth) batch={batch} seq={seq} steps={steps}", flush=True)
    out = train(harness, steps=steps, batch=batch, seq=seq, seed=seed,
                log_every=1)
    losses = out["losses"]
    print(f"[smoke] train compile_s={out['compile_s']:.3f} step_s="
          f"{[round(s, 4) for s in out['step_s']]} (smoke numbers)", flush=True)
    print(f"[smoke] train losses={[round(x, 4) for x in losses]}", flush=True)
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(bool(np.isfinite(losses).all()), "train loss not finite")
    print(f"[smoke] train ok; {memory_line(jax.devices()[0])}", flush=True)


def four_chip_phase(harness, devices, *, batch: int, seq: int, seed: int) -> None:
    """One train step on a 2x2 mesh of ``devices`` against the same step
    on ``devices[0]``, from the same seed and batch, both built by the
    launcher (``launch.train.setup_train``) as ``launch.train.train`` does."""
    import jax
    import numpy as np

    from repro.data.pipeline import DataConfig, SyntheticSource
    from repro.launch.hlo_stats import collective_stats
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.train import setup_train
    from repro.optim import adamw
    from repro.optim.compression import CompressionConfig
    from repro.parallel.sharding import dp_size

    cfg = harness.cfg
    mesh = make_smoke_mesh(2, 2, devices=devices)
    opt_cfg = adamw.OptConfig(warmup_steps=10, decay_steps=100)
    print(f"[smoke] four-chip {cfg.name}: layers={cfg.n_layers} batch={batch} "
          f"seq={seq} mesh={dict(mesh.shape)} dp={dp_size(mesh.shape)}", flush=True)

    raw = SyntheticSource(DataConfig(batch, seq, cfg.vocab_size, seed=seed)).batch_at(0)
    host_batch = {"tokens": raw[:, :-1], "labels": raw[:, 1:]}

    def build(mesh):
        return setup_train(harness, mesh, batch=batch, seq=seq, opt_cfg=opt_cfg,
                           compression=CompressionConfig(), seed=seed)

    def step(built):
        """(loss, gradient norm, parameter norm) of one step, and its time."""
        batch = built.feed(host_batch)
        t0 = time.perf_counter()
        params, _, metrics = jax.block_until_ready(
            built.step(built.params, built.opt_state, batch))
        step_s = time.perf_counter() - t0
        pnorm = float(jax.jit(adamw.global_norm)(params))
        return (float(metrics["loss"]), float(metrics["grad_norm"]), pnorm), step_s

    # sharded step
    sharded = build(mesh)
    stats = collective_stats(sharded.step.as_text())
    print(f"[smoke] four-chip compile_s={sharded.compile_s:.3f} collectives="
          f"{stats.count} by_kind="
          f"{ {k: int(v) for k, v in sorted(stats.by_kind.items())} }", flush=True)
    check(stats.count > 0, "no collectives in the sharded step")

    state = jax.tree.leaves((sharded.params, sharded.opt_state))
    spread = {d for leaf in state for d in leaf.sharding.device_set}
    split = sum(leaf.addressable_shards[0].data.shape != leaf.shape for leaf in state)
    print(f"[smoke] four-chip placement: devices={len(spread)} "
          f"split_leaves={split}/{len(state)}", flush=True)
    check(spread == set(devices), "state is not on all four devices")
    check(split > 0, "no parameter or optimizer leaf is split across devices")
    del state
    got, step_s = step(sharded)
    del sharded
    for d in devices:
        print(f"[smoke] four-chip {d}: {memory_line(d)}", flush=True)

    # the same step on one device
    want, _ = step(build(make_smoke_mesh(1, 1, devices=devices[:1])))

    print(f"[smoke] four-chip sharded step_s={step_s:.4f} (smoke number, first call)",
          flush=True)
    names = ("loss", "grad_norm", "param_norm")
    for name, g, w, tol in zip(names, got, want, (LOSS_RTOL, GNORM_RTOL, PNORM_RTOL)):
        rel = abs(g - w) / max(abs(w), 1e-12)
        print(f"[smoke] four-chip {name}: sharded={g:.6f} one_device={w:.6f} "
              f"rel={rel:.3e} (limit {tol})", flush=True)
        check(bool(np.isfinite(g)) and rel <= tol, f"{name} differs: {g} vs {w}")
    print("[smoke] four-chip ok", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2 sharded train step vs one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    all_devices = jax.devices()
    platform = all_devices[0].platform
    if platform != "tpu":
        print(f"[smoke] no TPU: jax.devices()[0].platform is {platform!r}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(all_devices) < need:
        print(f"[smoke] needs {need} chips, found {len(all_devices)}",
              file=sys.stderr)
        return 2
    devices = all_devices[:need]
    print(f"[smoke] device kind={devices[0].device_kind} "
          f"count={len(all_devices)}", flush=True)

    from repro.configs import load
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    harness = load(ARCH)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(harness.clone(n_layers=TRAIN_LAYERS), devices,
                            batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=args.seed)
        else:
            serve_phase(harness, batch=SERVE_BATCH, prompt_len=PROMPT_LEN,
                        gen=GEN, seed=args.seed, rtol=DECODE_RTOL)
            train_phase(harness.clone(n_layers=TRAIN_LAYERS), steps=TRAIN_STEPS,
                        batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(all_devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
