"""Device self time by the program's named scopes, read from a profiler
trace.

    python3 chipbench/scopes.py --workload <name> --seed <n> --seconds <s>

makes one traced run of the cell, as ``run.py --trace 1`` does, and reads
the trace before ``run.py`` removes it.  It prints the same result line on
stdout and, on stderr, for each of the program's modules: its self time by
scope, the shares below, and every op that holds at least 1% of it, with
its ``op_name`` path.

The program puts each op of its model step under one of ``VOCABULARY``'s
``jax.named_scope`` names, and the compiler keeps that name stack as the
op's ``op_name`` metadata.  The TPU's op events carry no such stat (only
their device offset and duration): the ``op_name`` of each op is read from
the optimised HLO of its program, which the trace's ``/host:metadata``
plane holds (``hlo_op_paths``).  Each instant of device time goes to one
op, the innermost: the latest started of those still running, so a
``while`` keeps only what its body leaves uncovered and the self times add
up to the device's busy time (``self_time``).

This is the benchmark's own copy of the vocabulary and imports nothing
from ``src/``: a scope the program renames or drops shows as time moved to
``unscoped``.
"""

from __future__ import annotations

import argparse
import heapq
import json
import re
import sys
from collections import defaultdict

import numpy as np

import devtrace

VOCABULARY = ("embed", "norm", "qkv", "kv_cache", "sdpa", "attn_out", "mlp",
              "layer_stack", "head", "loss", "optimizer")
UNSCOPED = "unscoped"
RECOMPUTE = "rematted_computation"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# the program's modules; ``jit_sample`` is the benchmark's own
PROGRAM = ("jit_train_step", "jit_prefill", "jit_decode")
HEAVY = 0.01   # ops listed by ``report``: at least this share of their module

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def components(name: str) -> list[str]:
    """``a/transpose(jvp(mlp))/dot_general`` -> ``[a, mlp, dot_general]``:
    transform wrappers stripped, ``jit(...)`` kept as the call it names."""
    out = []
    for part in name.split("/"):
        m = _WRAPPED.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def _root(path: str) -> str:
    """The first name of a ``;``-joined path (the compiler joins the names
    of the ops it merged into one)."""
    return path.split(";", 1)[0]


def scope_of(path: str) -> str:
    """The innermost vocabulary name of the path's root name, or
    ``unscoped``."""
    for part in reversed(components(_root(path))):
        if part in VOCABULARY:
            return part
    return UNSCOPED


def recomputed(path: str) -> bool:
    """Whether the op recomputes the forward pass for the backward
    (``jax.checkpoint``'s ``rematted_computation``)."""
    return RECOMPUTE in components(_root(path))


# per-layer shares of a module's self time: name -> (modules, predicate)
SHARES = {
    "sdpa_share.train": (("jit_train_step",), lambda p: scope_of(p) == "sdpa"),
    "head_loss_share.train": (("jit_train_step",), lambda p: scope_of(p) in ("head", "loss")),
    "recompute_share.train": (("jit_train_step",), recomputed),
    "unscoped_share.train": (("jit_train_step",), lambda p: scope_of(p) == UNSCOPED),
    "sdpa_share.prefill": (("jit_prefill",), lambda p: scope_of(p) == "sdpa"),
    "kv_cache_share.decode": (("jit_decode",), lambda p: scope_of(p) == "kv_cache"),
    "unscoped_share.serve": (("jit_prefill", "jit_decode"), lambda p: scope_of(p) == UNSCOPED),
}


def self_time(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each interval's share of the union: every instant goes to the
    interval that started last among those still running (of two that
    start together, the shorter), so the shares add up to the union."""
    n = len(starts)
    if n == 0:
        return np.zeros(0)
    order = np.lexsort((-ends, starts))      # rank: later start, inner
    rank = np.empty(n, int)
    rank[order] = np.arange(n)
    times = np.concatenate([ends, starts])
    is_start = np.concatenate([np.zeros(n, bool), np.ones(n, bool)])
    who = np.concatenate([rank, rank])
    sweep = np.lexsort((is_start, times))      # at a tie, ends first
    alive = [False] * n
    own = [0.0] * n
    holder = order.tolist()
    heap: list = []
    prev = 0.0
    for t, begins, r in zip(times[sweep].tolist(), is_start[sweep].tolist(),
                            who[sweep].tolist()):
        while heap and not alive[-heap[0]]:
            heapq.heappop(heap)
        if heap:
            own[holder[-heap[0]]] += t - prev
        prev = t
        alive[r] = begins
        if begins:
            heapq.heappush(heap, -r)
    return np.array(own)


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one serialized protobuf message: a varint
    as an int, any other field as a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _text(buf) -> str:
    return bytes(buf).decode()


def hlo_op_names(hlo_proto) -> dict:
    """HLO op -> op_name of every instruction of a serialized
    ``xla.HloProto`` (hlo_module 1 > computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2)."""
    names = {}
    for num, module in _fields(hlo_proto):
        if num != 1:
            continue
        for num, comp in _fields(module):
            if num != 3:
                continue
            for num, instr in _fields(comp):
                if num != 2:
                    continue
                name = path = ""
                for k, v in _fields(instr):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        path = next((_text(mv) for mk, mv in _fields(v) if mk == 2), "")
                names[name] = path
    return names


def hlo_op_paths(xspace: bytes) -> dict:
    """(module, HLO op) -> op_name, from the optimised HLO of each program
    that the ``/host:metadata`` plane of a serialized ``XSpace`` holds
    (planes 1 > name 2, event_metadata 4 > value 2 > name 2, stats 5 >
    metadata_id 1, bytes_value 6; stat_metadata 5 > value 2 > id 1, name
    2).  Programs that share a module name share one map."""
    out: dict = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        events, stat_names = [], {}
        for k, v in _fields(plane):
            if k == 2 and _text(v) != METADATA_PLANE:
                break
            if k == 4:
                events.extend(v2 for k2, v2 in _fields(v) if k2 == 2)
            elif k == 5:
                for k2, v2 in _fields(v):
                    if k2 == 2:
                        meta = dict(_fields(v2))
                        stat_names[meta.get(1)] = _text(meta.get(2, b""))
        for event in events:
            name, protos = "", []
            for k, v in _fields(event):
                if k == 2:
                    name = devtrace.module_name(_text(v))
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                for op, path in hlo_op_names(proto).items():
                    out.setdefault((name, op), path)
    return out


def op_table(planes, hlo_paths: dict) -> dict:
    """(module, HLO op) -> [self seconds, runs, op_name path] over the
    trace's window, mean over devices.  ``planes`` is what
    ``devtrace.reduce`` takes, ``hlo_paths`` what ``hlo_op_paths`` gives;
    an op it does not name has the path ""."""
    devices, _, (lo, hi) = devtrace.read_planes(planes)
    table: dict = defaultdict(lambda: [0.0, 0.0, ""])
    n = max(len(devices), 1)
    for dev in devices.values():
        s = np.array([o[1] for o in dev.ops], float)
        e = np.array([o[2] for o in dev.ops], float)
        mods = devtrace.in_modules(s, dev.modules)
        a, b = np.clip(s, lo, hi), np.clip(e, lo, hi)
        keep = np.flatnonzero(b > a)
        for i, t in zip(keep, self_time(a[keep], b[keep])):
            name = dev.ops[i][0]
            row = table[(mods[i], name)]
            row[0] += t * 1e-9 / n
            row[1] += 1 / n
            row[2] = hlo_paths.get((mods[i], name), "")
    return dict(table)


def share(table: dict, modules, pred) -> float | None:
    """Percentage of the named modules' self time whose path satisfies
    ``pred``; None where they have none, or none of it lies under a scope
    of the vocabulary (a program without the scopes)."""
    total = part = 0.0
    scoped = False
    for (module, _), (secs, _, path) in table.items():
        if module not in modules:
            continue
        total += secs
        scoped = scoped or scope_of(path) != UNSCOPED
        if pred(path):
            part += secs
    if total <= 0 or not scoped:
        return None
    return 100.0 * part / total


def report(table: dict) -> dict:
    """For each of the program's modules in ``table``: its self time, the
    seconds of each scope, and the ops that hold at least ``HEAVY`` of it;
    and every share of ``SHARES`` that can be read."""
    out: dict = {"modules": {}, "shares": {}}
    for module in PROGRAM:
        rows = {op: row for (mod, op), row in table.items() if mod == module}
        total = sum(r[0] for r in rows.values())
        if total <= 0:
            continue
        by_scope: dict = defaultdict(float)
        for secs, _, path in rows.values():
            by_scope[scope_of(path)] += secs
        heavy = sorted(((op, r) for op, r in rows.items() if r[0] >= HEAVY * total),
                       key=lambda kv: -kv[1][0])
        out["modules"][module] = {
            "self_s": total,
            "scopes": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
            "ops": [[op, secs, runs, path] for op, (secs, runs, path) in heavy],
        }
    for name, (modules, pred) in SHARES.items():
        value = share(table, modules, pred)
        if value is not None:
            out["shares"][name] = value
    return out


def main(argv=None) -> int:
    import run
    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    run.prepare_env()
    spec = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), args.workload)
    read: dict = {}

    def reduce_dir(log_dir: str):
        """``devtrace.reduce_dir``, keeping the op table of the same trace."""
        with open(devtrace.find_xplane(log_dir), "rb") as f:
            xspace = f.read()
        data = ProfileData.from_serialized_xspace(xspace)
        hlo_paths = hlo_op_paths(xspace)
        read["table"] = op_table(data.planes, hlo_paths)
        read["hlo_ops"] = len(hlo_paths)
        return devtrace.reduce(data.planes)

    devtrace.reduce_dir = reduce_dir
    code, line, outcome = run.run_cell(spec, args.seed, args.seconds, True)
    if "table" in read:
        table = read["table"]
        rep = report(table)
        rep["hlo_ops"] = read["hlo_ops"]
        rep["self_s"] = sum(r[0] for r in table.values())
        rep["traced_metrics"] = outcome.metrics if outcome is not None else None
        print("scopes " + json.dumps(run.finite(rep)), file=sys.stderr, flush=True)
    if line is not None:
        print(json.dumps(run.finite(line)), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
