"""Reduction of a JAX profiler trace to device busy time, module and op
totals, idle gaps and exposed collective time.

The trace is the ``.xplane.pb`` file that ``jax.profiler`` writes, read
with ``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation run, their ``XLA
Modules`` line one event per program run.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane; the span named
``window`` bounds the measured window, and every figure here is clipped
to it.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
HOST_SPANS = ("data", "dispatch", "sample", "sync")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals; returns the sorted, disjoint (starts, ends)."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    out_s = s[idx]
    out_e = reach[np.append(idx[1:] - 1, len(s) - 1)]
    return out_s, out_e


def clip(starts, ends, lo, hi):
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    return s[keep], e[keep]


def gaps(busy_s, busy_e, lo, hi):
    """Complement of disjoint sorted intervals within [lo, hi]."""
    edges_s = np.concatenate([[lo], busy_e])
    edges_e = np.concatenate([busy_s, [hi]])
    keep = edges_e > edges_s
    return edges_s[keep], edges_e[keep]


def covered(starts, ends, lo, hi) -> float:
    """Length of [lo, hi] covered by disjoint intervals sorted by start."""
    i0 = int(np.searchsorted(ends, lo, side="right"))
    i1 = int(np.searchsorted(starts, hi, side="left"))
    if i1 <= i0:
        return 0.0
    s, e = clip(starts[i0:i1], ends[i0:i1], lo, hi)
    return float(np.sum(e - s))


@dataclass
class Device:
    ops: list = field(default_factory=list)        # (name, start_ns, end_ns)
    modules: list = field(default_factory=list)    # (name, start_ns, end_ns)


@dataclass
class Reduced:
    """What the per-layer readers take from one trace; times in seconds."""

    window_s: float
    busy_s: float                   # union of op intervals, mean over devices
    devices: int
    modules: dict                   # name -> [runs, seconds], mean over devices
    ops: dict                       # op name -> seconds, mean over devices
    idle_by_span: dict              # host span -> idle seconds, mean over devices
    idle_gaps: int                  # number of idle intervals, device 0
    collective_s: float             # collective op time, mean over devices
    collective_exposed_s: float     # of it, with no other op running
    starts: dict = field(default_factory=dict)  # module name -> run starts (s), device 0

    def module(self, prefix: str) -> tuple[int, float]:
        """(runs, seconds) of the modules whose name starts with ``prefix``."""
        runs, secs = 0, 0.0
        for name, (n, s) in self.modules.items():
            if name.startswith(prefix):
                runs += n
                secs += s
        return runs, secs

    def period(self, name: str, breaks: tuple = ()) -> tuple[int, float]:
        """(intervals, seconds) between the starts of consecutive runs of
        module ``name`` on the first device, leaving out the intervals in
        which a module of ``breaks`` starts: the whole step on the device
        clock, idle time and the small programs between included."""
        runs = self.starts.get(name, [])
        cuts = sorted(t for b in breaks for t in self.starts.get(b, []))
        n, total = 0, 0.0
        for a, b in zip(runs, runs[1:]):
            i = np.searchsorted(cuts, a, side="right")
            if i < len(cuts) and cuts[i] < b:
                continue
            n += 1
            total += b - a
        return n, total

    def idle_percent(self) -> float | None:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def module_name(name: str) -> str:
    """``jit_prefill(123)`` -> ``jit_prefill``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(name: str) -> str:
    """An op event's name, which may be the whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``), cut to the op: ``fusion.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def in_modules(op_starts: np.ndarray, modules: list) -> list[str]:
    """The module each op started in (``?`` where none holds it): op names
    repeat from one compiled module to the next."""
    if not modules:
        return ["?"] * len(op_starts)
    mods = sorted(modules, key=lambda m: m[1])
    ms = np.array([m[1] for m in mods], float)
    me = np.array([m[2] for m in mods], float)
    idx = np.searchsorted(ms, op_starts, side="right") - 1
    return [mods[i][0] if i >= 0 and a < me[i] else "?" for i, a in zip(idx, op_starts)]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_planes(planes) -> tuple[dict, list, tuple[int, int]]:
    """Devices, host spans and the window from the planes of a trace.

    ``planes`` is ``ProfileData.planes`` or anything shaped like it:
    objects with ``name`` and ``lines``, lines with ``name`` and
    ``events``, events with ``name``, ``start_ns`` and ``duration_ns``.
    """
    devices: dict[int, Device] = {}
    spans: list[tuple[str, float, float]] = []
    window = None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                                   for ev in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend((module_name(ev.name), ev.start_ns,
                                        ev.start_ns + ev.duration_ns)
                                       for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        w = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        if window is None or w[1] - w[0] > window[1] - window[0]:
                            window = w
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return devices, spans, window


def reduce(planes) -> Reduced:
    devices, spans, (lo, hi) = read_planes(planes)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    n = len(devices)
    # spans of one name follow each other on the loop's thread: sorted by
    # start, they are disjoint, which ``covered`` relies on
    span_arr = {name: union(np.array([s for k, s, _ in spans if k == name], float),
                            np.array([e for k, _, e in spans if k == name], float))
                for name in HOST_SPANS}
    busy = 0.0
    modules: dict = defaultdict(lambda: [0, 0.0])
    ops: dict = defaultdict(float)
    idle_by_span: dict = defaultdict(float)
    coll_total = coll_exposed = 0.0
    n_gaps = 0
    starts: dict = defaultdict(list)
    for index, dev in sorted(devices.items()):
        s = np.array([o[1] for o in dev.ops], float)
        e = np.array([o[2] for o in dev.ops], float)
        names = [o[0] for o in dev.ops]
        cs, ce = clip(s, e, lo, hi)
        us, ue = union(cs, ce)
        busy += float(np.sum(ue - us))
        for module, name, a, b in zip(in_modules(s, dev.modules), names, s, e):
            d = min(b, hi) - max(a, lo)
            if d > 0:
                ops[f"{module}/{name}"] += d
        for name, a, b in dev.modules:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                modules[name][0] += 1
                modules[name][1] += d
                if index == min(devices) and a >= lo:
                    starts[name].append(a * 1e-9)
        gs, ge = gaps(us, ue, lo, hi)
        if index == min(devices):
            n_gaps = len(gs)
        for a, b in zip(gs, ge):
            best, best_cover = "other", 0.0
            for span, (ss, se) in span_arr.items():
                c = covered(ss, se, a, b)
                if c > best_cover:
                    best, best_cover = span, c
            idle_by_span[best] += b - a
        is_coll = np.array([bool(COLLECTIVE.search(x)) for x in names], bool)
        if is_coll.any():
            ks, ke = clip(s[is_coll], e[is_coll], lo, hi)
            ks, ke = union(ks, ke)
            os_, oe = union(*clip(s[~is_coll], e[~is_coll], lo, hi))
            coll_total += float(np.sum(ke - ks))
            for a, b in zip(ks, ke):
                coll_exposed += (b - a) - covered(os_, oe, a, b)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy * ns / n,
        devices=n,
        modules={k: [v[0] / n, v[1] * ns / n] for k, v in modules.items()},
        ops={k: v * ns / n for k, v in ops.items()},
        idle_by_span={k: v * ns / n for k, v in idle_by_span.items()},
        idle_gaps=n_gaps,
        collective_s=coll_total * ns / n,
        collective_exposed_s=coll_exposed * ns / n,
        starts={k: sorted(v) for k, v in starts.items()},
    )


def reduce_dir(log_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(log_dir)).planes)
