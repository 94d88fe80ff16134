"""The trace reduction on a small recorded trace with hand-counted answers."""

from dataclasses import dataclass, field

import pytest

import devtrace


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


def recorded():
    """Window [100, 1100] ns.  Device 0: ops [50,150] (clipped to
    [100,150]), [140,300] overlapping it, [400,500], all-reduce [450,700]
    (half hidden under the op), [900,1200] clipped to [900,1100].  Host:
    dispatch [150,400], sync [700,900]."""
    host = Plane("/host:CPU", [Line("python", [
        Ev("window", 100, 1000), Ev("dispatch", 150, 250), Ev("sync", 700, 200)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(7)", 50, 450), Ev("jit_step(7)", 450, 650)]),
        Line("XLA Ops", [Ev("fusion.1", 50, 100), Ev("convolution.2", 140, 160),
                         Ev("fusion.1", 400, 100), Ev("all-reduce.3", 450, 250),
                         Ev("convolution.2", 900, 300)]),
    ])
    other = Plane("/device:TPU:0 SparseCore 0", [Line("XLA Ops", [Ev("x", 0, 5000)])])
    return [host, dev, other]


def test_reduce_hand_counts():
    r = devtrace.reduce(recorded())
    ns = 1e-9
    assert r.window_s == pytest.approx(1000 * ns)
    # busy: [100,300] + [400,700] + [900,1100] = 200 + 300 + 200
    assert r.busy_s == pytest.approx(700 * ns)
    assert r.idle_percent() == pytest.approx(30.0)
    # idle [300,400] under dispatch, [700,900] under sync
    assert r.idle_by_span == pytest.approx({"dispatch": 100 * ns, "sync": 200 * ns})
    assert r.idle_gaps == 2
    assert r.ops["jit_step/convolution.2"] == pytest.approx((160 + 200) * ns)
    assert r.ops["jit_step/fusion.1"] == pytest.approx((50 + 100) * ns)
    assert r.module("jit_step") == (2, pytest.approx((400 + 650) * ns))
    # all-reduce [450,700]; fusion covers [450,500]
    assert r.collective_s == pytest.approx(250 * ns)
    assert r.collective_exposed_s == pytest.approx(200 * ns)
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_step/convolution.2", pytest.approx(360 * ns)]
    assert b["idle_gaps"][0] == ["sync", pytest.approx(200 * ns)]


def test_ops_are_named_by_module_and_op():
    """The trace may name an op by its whole HLO instruction, and op names
    repeat from one module to the next."""
    host = Plane("/host:CPU", [Line("python", [Ev("window", 0, 100)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_prefill(3)", 0, 40), Ev("jit_decode(4)", 50, 40)]),
        Line("XLA Ops", [Ev("%while.3 = (s32[], bf16[16,1,4096]) while(%tuple.3)", 0, 30),
                         Ev("%while.3 = (s32[], bf16[4,1,2048]) while(%tuple.9)", 50, 20),
                         Ev("copy.1", 95, 5)])])
    r = devtrace.reduce([host, dev])
    assert r.ops == pytest.approx({"jit_prefill/while.3": 30e-9, "jit_decode/while.3": 20e-9,
                                   "?/copy.1": 5e-9})


def test_union_merges_overlaps_and_touching():
    import numpy as np

    s, e = devtrace.union(np.array([5., 0., 2., 10.]), np.array([7., 3., 4., 11.]))
    assert s.tolist() == [0., 5., 10.] and e.tolist() == [4., 7., 11.]


def test_no_window_span_is_an_error():
    planes = recorded()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        devtrace.reduce(planes)


def test_period_skips_intervals_cut_by_another_module():
    host = Plane("/host:CPU", [Line("python", [Ev("window", 100, 1000)])])
    dev = Plane("/device:TPU:0", [Line("XLA Modules", [
        Ev("jit_decode(1)", 100, 20), Ev("jit_decode(1)", 200, 20),
        Ev("jit_decode(1)", 300, 20), Ev("jit_prefill(2)", 350, 30),
        Ev("jit_decode(1)", 400, 20), Ev("jit_decode(1)", 500, 20),
    ]), Line("XLA Ops", [Ev("fusion.1", 100, 20)])])
    r = devtrace.reduce([host, dev])
    ns = 1e-9
    # 100->200, 200->300, 400->500; 300->400 holds the prefill
    assert r.period("jit_decode", breaks=("jit_prefill",)) == (3, pytest.approx(300 * ns))
    assert r.period("jit_decode") == (4, pytest.approx(400 * ns))
    assert r.period("jit_absent") == (0, 0.0)
