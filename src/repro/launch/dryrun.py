import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count on first init).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, both meshes

Each cell writes ``results/dryrun/<arch>__<shape>__<mesh>.json`` with
memory_analysis, cost_analysis, collective wire bytes, and roofline terms.
"""

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, load
from repro.launch.hlo_stats import Roofline, collective_stats
from repro.launch.mesh import make_production_mesh
from repro.models.api import SHAPES
from repro.models.param import param_count
from repro.train.train_step import build_bundle, lower_bundle

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def analytic_model_flops(harness, cell) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), per device."""
    n_params = param_count(harness.param_specs())
    cfg = harness.cfg
    moe = getattr(cfg, "moe", None)
    if moe is not None:
        # embedding + attention stay dense; experts scale by topk/E
        expert_frac = 0.0
        from repro.models.moe import moe_specs

        expert_params = param_count(moe_specs(cfg.d_model, moe)) * cfg.n_layers
        active = n_params - expert_params + expert_params * moe.topk / moe.n_experts
    else:
        active = n_params
    tokens = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        total = 6.0 * active * tokens
    elif cell.kind == "prefill":
        total = 2.0 * active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * active * cell.global_batch
    return total


def _cost_dict(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()`` to one flat dict.

    Depending on the jax/XLA version the call returns a dict, a list of
    per-device dicts (we want device 0: SPMD devices are identical), or
    None when analysis is unavailable.
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if cost else {}


def _probe_metrics(harness, cell, mesh) -> dict:
    """Compile one UNROLLED probe and return its per-device counters.

    XLA's cost analysis visits while-loop (lax.scan) bodies once, so the
    official scanned compile undercounts FLOPs/bytes/collectives by the trip
    count.  Probes unroll all loops at reduced depth/length, then the caller
    extrapolates with the known cost structure (linear in layers; linear in
    chunks for SSM scans; attention's S^2 captured exactly at full S or via
    a quadratic fit for the hybrid's shared block).
    """
    bundle = build_bundle(harness, cell, mesh)
    compiled = lower_bundle(bundle, mesh).compile()
    cost = _cost_dict(compiled)
    coll = collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm": float(cost.get("bytes accessed", 0.0)),
        "wire": float(coll.wire_bytes),
        "ops": coll.count,
        "by_kind": dict(coll.by_kind),
    }


def _probe_cell(cell, seq_len):
    import dataclasses

    return dataclasses.replace(cell, seq_len=seq_len)


def extrapolated_metrics(harness, cell, mesh) -> dict:
    """Per-device (flops, hbm bytes, wire bytes) at the FULL (L, S)."""
    fam = harness.family
    keys = ("flops", "hbm", "wire")

    def probe(L, S=None, **extra):
        h = harness.clone(n_layers=L, unroll=True, **extra)
        c = cell if S is None else _probe_cell(cell, S)
        return _probe_metrics(h, c, mesh)

    if fam in ("dense", "moe", "vlm", "audio"):
        L_full = harness.cfg.n_layers
        f1, f2 = probe(1), probe(2)
        out = {k: f1[k] + (L_full - 1) * (f2[k] - f1[k]) for k in keys}
        out["by_kind"] = {
            kk: f1["by_kind"].get(kk, 0.0)
            + (L_full - 1) * (f2["by_kind"].get(kk, 0.0) - f1["by_kind"].get(kk, 0.0))
            for kk in set(f1["by_kind"]) | set(f2["by_kind"])
        }
        return out

    if fam == "ssm":
        L_full, S_full = harness.cfg.n_layers, cell.seq_len
        if cell.kind == "decode":
            f1, f2 = probe(1), probe(2)
            return {k: f1[k] + (L_full - 1) * (f2[k] - f1[k]) for k in keys}
        S0 = min(256, S_full)
        pts = {(L, S): probe(L, S) for L in (1, 2) for S in (S0, 2 * S0)}
        out = {}
        for k in keys:
            P1 = pts[(2, S0)][k] - pts[(1, S0)][k]       # per-layer @ S0
            P2 = pts[(2, 2 * S0)][k] - pts[(1, 2 * S0)][k]
            p1 = (P2 - P1) / S0
            p0 = P1 - S0 * p1
            E1 = pts[(1, S0)][k] - P1
            E2 = pts[(1, 2 * S0)][k] - P2
            e1 = (E2 - E1) / S0
            e0 = E1 - S0 * e1
            out[k] = e0 + e1 * S_full + L_full * (p0 + p1 * S_full)
        return out

    if fam == "hybrid":
        # F(L, S) = E(S) + n_mamba(L) * M(S) + n_shared(L) * A(S)
        # probes L in {6, 7, 8}: n_shared = 0, 1, 1 so
        #   M = F8 - F7,  A = (F7 - F6) - M,  E = F6 - 6M
        L_full = harness.cfg.n_layers
        S_full = cell.seq_len
        n_shared_full = sum(
            1
            for d in range(1, L_full)
            if d % harness.cfg.share_every == 0
        )

        def solve(S=None):
            f6, f7, f8 = probe(6, S), probe(7, S), probe(8, S)
            sol = {}
            for k in keys:
                M = f8[k] - f7[k]
                A = (f7[k] - f6[k]) - M
                E = f6[k] - 6 * M
                sol[k] = (E, M, A)
            return sol

        if cell.kind == "decode":
            sol = solve()
            return {
                k: sol[k][0] + L_full * sol[k][1] + n_shared_full * sol[k][2]
                for k in keys
            }
        Ss = [s for s in (256, 512, 1024) if s <= S_full] or [S_full]
        sols = {S: solve(S) for S in Ss}
        import numpy as np

        out = {}
        for k in keys:
            Es = np.array([sols[S][k][0] for S in Ss])
            Ms = np.array([sols[S][k][1] for S in Ss])
            As = np.array([sols[S][k][2] for S in Ss])
            Sv = np.array(Ss, dtype=float)
            ce = np.polyfit(Sv, Es, min(1, len(Ss) - 1))
            cm = np.polyfit(Sv, Ms, min(1, len(Ss) - 1))
            ca = np.polyfit(Sv, As, min(2, len(Ss) - 1))
            E = float(np.polyval(ce, S_full))
            M = float(np.polyval(cm, S_full))
            A = float(np.polyval(ca, S_full))
            out[k] = E + L_full * M + n_shared_full * A
        return out

    raise ValueError(f"unknown family {fam}")


def run_cell(arch: str, shape: str, multi_pod: bool, probes: bool = True) -> dict:
    harness = load(arch)
    cell = SHAPES[shape]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "kind": cell.kind,
        "status": "ok",
    }
    skip = harness.skip_reason(shape)
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec

    chips = 512 if multi_pod else 256
    mesh = make_production_mesh(multi_pod=multi_pod)

    # ---- official artifact: the scanned full-depth program ----------------
    t0 = time.time()
    bundle = build_bundle(harness, cell, mesh)
    lowered = lower_bundle(bundle, mesh)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_per_device_gb": round(
            (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9, 3
        ),
    }

    # ---- cost counters: probe-extrapolated (see _probe_metrics docstring) -
    t2 = time.time()
    if probes:
        metrics = extrapolated_metrics(harness, cell, mesh)
    else:
        cost = _cost_dict(compiled)
        coll = collective_stats(compiled.as_text())
        metrics = {
            "flops": float(cost.get("flops", 0.0)),
            "hbm": float(cost.get("bytes accessed", 0.0)),
            "wire": float(coll.wire_bytes),
        }
        rec["counters"] = "scanned-only (loop bodies counted once; LOWER BOUND)"
    rec["probe_s"] = round(time.time() - t2, 1)

    model_flops_total = analytic_model_flops(harness, cell)
    roof = Roofline(
        flops=metrics["flops"],
        hbm_bytes=metrics["hbm"],
        wire_bytes=metrics["wire"],
        model_flops=model_flops_total / chips,
    )
    rec["cost"] = {
        "flops_per_device": metrics["flops"],
        "hbm_bytes_per_device": metrics["hbm"],
    }
    rec["collectives"] = {
        "wire_bytes_per_device": metrics["wire"],
        "by_kind": metrics.get("by_kind", {}),
    }
    rec["roofline"] = roof.to_dict()
    rec["params"] = param_count(harness.param_specs())
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape, False))
                cells.append((arch, shape, True))
    else:
        arches = [args.arch] if args.arch else ARCH_IDS
        shapes = [args.shape] if args.shape else list(SHAPES)
        meshes = [args.multi_pod] if not args.both_meshes else [False, True]
        for a in arches:
            for s in shapes:
                for m in meshes:
                    cells.append((a, s, m))

    failures = 0
    for arch, shape, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        out = RESULTS / f"{arch.replace('-', '_')}__{shape}__{mesh_name}.json"
        if out.exists() and not args.force:
            rec = json.loads(out.read_text())
            if rec.get("status") in ("ok", "skipped"):
                print(f"[dryrun] {arch:16s} {shape:12s} {mesh_name:10s} cached",
                      flush=True)
                continue
        try:
            rec = run_cell(arch, shape, mp, probes=not args.no_probes)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            rec = {
                "arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            failures += 1
        out.write_text(json.dumps(rec, indent=2))
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (
                f" mem={rec['memory']['peak_per_device_gb']}GB"
                f" flops/dev={rec['cost']['flops_per_device']:.3e}"
                f" wire/dev={rec['collectives']['wire_bytes_per_device']:.3e}B"
                f" bottleneck={rec['roofline']['bottleneck']}"
                f" compile={rec['compile_s']}s"
            )
        elif status == "skipped":
            extra = f" ({rec['reason'][:60]})"
        print(f"[dryrun] {arch:16s} {shape:12s} {mesh_name:10s} {status}{extra}",
              flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
