"""Readings that a cell's limits are set from: the program's compared
numbers over many seeds (the lower readings), and the same numbers for
the control and the planted faults (the upper readings).

    python3 chipbench/calibrate.py --workload <name> --seeds 101-112 \
        --variants fp8,half_batch [--seconds 0.5]

Each seed is one run of the cell through ``run.run_cell`` with a short
window (serving finishes as many requests as a run compares), in one
process so that set-up is paid once.  Variants: ``fp8``, the reference
computed in float8 in the program's place (the control), and for
training ``half_batch``, the reference over half of each batch.  Prints
one JSON line a seed and, last, the largest program reading and the
smallest variant reading of each number.  The benchmark's own runs do not
run it.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9,2147483700")
    ap.add_argument("--variants", default="fp8")
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)

    run.prepare_env()
    spec = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), args.workload)
    variants = tuple(v for v in args.variants.split(",") if v)
    lower: dict = {}
    upper: dict = {}
    for seed in seeds(args.seeds):
        code, line, outcome = run.run_cell(spec, seed, args.seconds, False, variants=variants)
        if code:
            return code
        print(json.dumps(run.finite({"seed": seed, "correct": line["correct"],
                                     "numbers": outcome.numbers,
                                     "variants": outcome.variants,
                                     "detail": outcome.detail})), flush=True)
        for name, value in outcome.numbers.items():
            lower[name] = max(lower.get(name, 0.0), value)
        for variant, numbers in outcome.variants.items():
            for name, value in numbers.items():
                key = f"{variant}.{name}"
                upper[key] = min(upper.get(key, float("inf")), value)
    print(json.dumps(run.finite({"lower": lower, "upper": upper})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
