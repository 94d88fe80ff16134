import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing: hypothesis -> change -> measure -> validate.

Runs the three chosen cells (worst roofline fraction / most collective-bound
/ most representative of the paper's technique) through their variant
ladders, measuring the probe-extrapolated roofline terms for each change.

    PYTHONPATH=src python -m benchmarks.hillclimb [--cell granite] [--quick]
    PYTHONPATH=src python -m benchmarks.hillclimb --perf-model netsim

``--perf-model`` re-prices each variant's collective wire bytes on a
``core.perf_model.PerfModel`` backend's UB-Mesh model axis (analytic
idealized bandwidth, or the netsim-calibrated effective bandwidth), shown
as the ``ub_coll`` column — what the variant's collective term would cost
on the paper's fabric instead of the v5e ICI constant.

Writes results/perf/<cell>__<variant>.json; EXPERIMENTS.md §Perf narrates
the hypothesis log.
"""

import argparse
import dataclasses
import json
import pathlib
import time

from repro.configs import load
from repro.launch.dryrun import extrapolated_metrics
from repro.launch.hlo_stats import Roofline
from repro.launch.mesh import make_production_mesh
from repro.models.api import SHAPES
from repro.models.moe import MoEConfig
from repro.train.train_step import build_bundle, lower_bundle

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "perf"


def _dbrx_moe(**kw) -> MoEConfig:
    return MoEConfig(
        n_experts=16, topk=4, d_ff=10752, strategy="expert_parallel", **kw
    )


# variant ladders: (name, hypothesis, cfg overrides)
CELLS = {
    "granite": (
        "granite-8b", "train_4k",
        [
            ("baseline", "paper-faithful: reference attention, full remat", {}),
            ("blocked-attn",
             "H-mem: S^2 score materialization dominates HBM bytes; blocked "
             "online-softmax attention removes it -> memory term down",
             {"attn_impl": "blocked"}),
            ("remat-dots",
             "H-coll: FSDP weight re-gathers run 3x (fwd+bwd+remat); saving "
             "matmul outputs drops the remat re-gather -> wire down, memory up",
             {"remat_policy": "dots"}),
            ("blocked+dots",
             "H-combo: the two compose (different terms)",
             {"attn_impl": "blocked", "remat_policy": "dots"}),
        ],
    ),
    "starcoder2": (
        "starcoder2-7b", "prefill_32k",
        [
            ("baseline", "paper-faithful reference attention", {}),
            ("blocked-attn",
             "H-swa: SWA(4096) computed as full 32K attention wastes 7/8 of "
             "blocks; static block skipping cuts FLOPs ~4x and HBM bytes more",
             {"attn_impl": "blocked"}),
        ],
    ),
    "dbrx": (
        "dbrx-132b", "train_4k",
        [
            ("baseline", "paper-faithful GShard MoE over seq-sharded tokens", {}),
            ("a2a-dispatch",
             "H-a2a: dispatch contracts the model-sharded seq dim -> GSPMD "
             "emits full (E,B,C,D) psums; resharding tokens seq->d_model "
             "turns the expert switch into an A2A (paper's own EP pattern)",
             {"moe": _dbrx_moe(reshard_tokens=True)}),
            ("bf16-dispatch",
             "H-dtype: dispatch/combine collectives carry f32; bf16 payloads "
             "halve wire bytes",
             {"moe": _dbrx_moe(dispatch_dtype="bf16")}),
            ("a2a+bf16+cap1.0",
             "H-combo: A2A lowering + bf16 payloads + capacity 1.0 "
             "(25% fewer dispatched tokens)",
             {"moe": _dbrx_moe(reshard_tokens=True, dispatch_dtype="bf16",
                               capacity_factor=1.0)}),
            ("round2+blocked",
             "round 2 (memory now dominant): add blocked attention to the "
             "best combo -> S^2 scores and mask temporaries gone",
             {"attn_impl": "blocked",
              "moe": _dbrx_moe(reshard_tokens=True, dispatch_dtype="bf16",
                               capacity_factor=1.0)}),
        ],
    ),
}


def measure(arch: str, shape: str, overrides: dict, multi_pod=False) -> dict:
    harness = load(arch)
    if overrides:
        harness = harness.clone(**overrides)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256

    t0 = time.time()
    bundle = build_bundle(harness, cell, mesh)
    compiled = lower_bundle(bundle, mesh).compile()
    mem = compiled.memory_analysis()
    peak_gb = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / 1e9

    metrics = extrapolated_metrics(harness, cell, mesh)
    from repro.launch.dryrun import analytic_model_flops

    roof = Roofline(
        flops=metrics["flops"],
        hbm_bytes=metrics["hbm"],
        wire_bytes=metrics["wire"],
        model_flops=analytic_model_flops(harness, cell) / chips,
    )
    return {
        "arch": arch, "shape": shape,
        "roofline": roof.to_dict(),
        "peak_gb": round(peak_gb, 2),
        "wall_s": round(time.time() - t0, 1),
    }


def ubmesh_model_axis_gbs(backend: str) -> float:
    """Per-chip model-axis bandwidth from a PerfModel backend — the price a
    variant's collective wire bytes would pay on the UB-Mesh fabric."""
    from repro.core.cost_model import Routing, build_comm_model

    comm = build_comm_model(multi_pod=False, routing=Routing.DETOUR)
    if backend == "netsim":
        from repro.core.perf_model import NetsimPerfModel

        perf = NetsimPerfModel(comm)
    else:
        perf = comm
    return perf.comm_model(None).axes["model"].gbs_per_chip


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None, choices=[*CELLS, None])
    ap.add_argument(
        "--perf-model", default=None, choices=("analytic", "netsim"),
        help="also price collective wire bytes on this UB-Mesh PerfModel backend",
    )
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)
    ub_gbs = ubmesh_model_axis_gbs(args.perf_model) if args.perf_model else None

    cells = [args.cell] if args.cell else list(CELLS)
    for cname in cells:
        arch, shape, ladder = CELLS[cname]
        print(f"=== {cname}: {arch} / {shape} ===", flush=True)
        base_terms = None
        for vname, hypothesis, overrides in ladder:
            out = RESULTS / f"{cname}__{vname}.json"
            if out.exists():
                rec = json.loads(out.read_text())
                print(f"  [cached] {vname}")
            else:
                rec = measure(arch, shape, overrides)
                rec["variant"] = vname
                rec["hypothesis"] = hypothesis
                out.write_text(json.dumps(rec, indent=2))
            r = rec["roofline"]
            terms = (r["compute_s"], r["memory_s"], r["collective_s"])
            if base_terms is None:
                base_terms = terms
            deltas = tuple(
                f"{(t / b - 1) * 100:+.1f}%" if b else "n/a"
                for t, b in zip(terms, base_terms)
            )
            ub = (
                f"ub_coll={r['wire_bytes'] / (ub_gbs * 1e9):.3f}s "
                if ub_gbs
                else ""
            )
            print(f"  {vname:18s} comp={terms[0]:.3f}s ({deltas[0]}) "
                  f"mem={terms[1]:.3f}s ({deltas[1]}) "
                  f"coll={terms[2]:.3f}s ({deltas[2]}) {ub}"
                  f"useful={r['useful_flops_ratio']:.2f} "
                  f"peak={rec['peak_gb']}GB", flush=True)


if __name__ == "__main__":
    main()
