"""Device time of collective ops by kind and by the program scope each
sits in, read from a profiler trace.

    python3 chipbench/collectives.py --workload <name> --seed <n> --seconds <s>

makes one traced run of the cell as ``scopes.py`` does (same arguments,
same result line on stdout, same ``scopes`` line on stderr) and prints
one more line on stderr, starting ``collectives ``: JSON, per program
module and collective kind, in the window and as the mean over devices,

- ``ops``: how many collective ops of the kind ran;
- ``time_s``: their device time;
- ``exposed_s``: the part of it in which no other op ran, so that the
  step waited for it; control flow (``while``, ``conditional``,
  ``call``) is no such op, since its event spans the ops of its body;
- ``self_s``: their self time as ``scopes.op_table`` gives it (each
  instant to the latest-started op still running, so a collective that
  starts after a compute op keeps the time they share);
- ``scopes``: ``[time_s, exposed_s]`` by program scope.

The compiler puts the collectives of a sharded step in itself and gives
each the ``op_name`` of the operation that needed it, so a gather of the
MLP's weights sits under ``mlp`` and the gradients' reduction under the
scope of the matmul whose gradient it sums.  Where two collectives run at
once each counts its own time; ``devtrace.Reduced.collective_s`` counts
their union.

The TPU compiler turns many gathers into asynchronous ones: an
``async-collective-start`` op issues the transfer, the matmul fusions
after it run while the data moves, and an ``async-collective-done`` op
waits for what is left.  Their names do not say which collective they
carry, so they count as kind ``async-collective``, and their time is the
issue and the wait, not the transfer that compute hid.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

import numpy as np

import devtrace
import scopes

# first match wins: "all-reduce-scatter" is a reduce-scatter
KINDS = ("reduce-scatter", "all-gather", "all-reduce", "collective-permute", "all-to-all",
         "async-collective")
# ops whose event spans the ops they run, not work of their own
CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def kind_of(op: str) -> str | None:
    """The collective kind an HLO op's name names, or None."""
    return next((k for k in KINDS if k in op), None)


def by_kind(planes, hlo_paths: dict, table: dict) -> dict:
    """module -> kind -> {"ops", "time_s", "exposed_s", "self_s",
    "scopes": {scope: [time_s, exposed_s]}} for the collective ops of a
    trace.  ``planes`` and ``hlo_paths`` are what ``scopes.op_table``
    takes, ``table`` what it gives for them."""
    devices, _, (lo, hi) = devtrace.read_planes(planes)
    n = max(len(devices), 1)
    out: dict = defaultdict(dict)

    def row(module: str, kind: str) -> dict:
        return out[module].setdefault(kind, {"ops": 0.0, "time_s": 0.0, "exposed_s": 0.0,
                                             "self_s": 0.0,
                                             "scopes": defaultdict(lambda: [0.0, 0.0])})

    for dev in devices.values():
        s = np.array([o[1] for o in dev.ops], float)
        e = np.array([o[2] for o in dev.ops], float)
        kinds = [kind_of(o[0]) for o in dev.ops]
        coll = np.array([k is not None for k in kinds], bool)
        if not coll.any():
            continue
        work = ~coll & np.array([not CONTROL.match(o[0]) for o in dev.ops], bool)
        other_s, other_e = devtrace.union(*devtrace.clip(s[work], e[work], lo, hi))
        mods = devtrace.in_modules(s, dev.modules)
        for i in np.flatnonzero(coll):
            a, b = max(s[i], lo), min(e[i], hi)
            if b <= a:
                continue
            t = (b - a) * 1e-9 / n
            x = t - devtrace.covered(other_s, other_e, a, b) * 1e-9 / n
            r = row(mods[i], kinds[i])
            r["ops"] += 1 / n
            r["time_s"] += t
            r["exposed_s"] += x
            scope = r["scopes"][scopes.scope_of(hlo_paths.get((mods[i], dev.ops[i][0]), ""))]
            scope[0] += t
            scope[1] += x
    for (module, op), (secs, _, _) in table.items():
        kind = kind_of(op)
        if kind is not None:
            row(module, kind)["self_s"] += secs
    return {module: {kind: dict(r, scopes=dict(sorted(r["scopes"].items(),
                                                       key=lambda kv: -kv[1][0])))
                     for kind, r in sorted(kinds.items())}
            for module, kinds in out.items()}


def keeping(reports: list):
    """``scopes.op_table`` as it is now, also putting ``by_kind`` of the
    same trace into ``reports``.  The profiler's ``planes`` can be walked
    only once, so they are listed first."""
    op_table = scopes.op_table

    def op_table_and_collectives(planes, hlo_paths):
        planes = list(planes)
        table = op_table(planes, hlo_paths)
        reports.append(by_kind(planes, hlo_paths, table))
        return table

    return op_table_and_collectives


def main(argv=None) -> int:
    import run

    reports: list = []
    op_table = scopes.op_table
    scopes.op_table = keeping(reports)
    try:
        code = scopes.main(argv)
    finally:
        scopes.op_table = op_table
    if reports:
        print("collectives " + json.dumps(run.finite(reports[-1])), file=sys.stderr, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
