"""The collective reader (``collective_share.train``) and
``collectives.py`` on small recorded traces of a two-device step: one
where the collectives run beside compute, one where nothing hides them,
and one shaped as the TPU records a sharded step, with the layer loop's
``while`` spanning its body and an asynchronous gather in it."""

from dataclasses import dataclass, field

import pytest

import collectives
import devtrace
import run
import scopes


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


def step_trace(overlap: bool):
    """Window [0, 1000] ns; on each of two devices one ``jit_train_step``
    run over [0, 1000] with a matmul [0, 600] and a gradient all-reduce of
    300 ns, then a weight all-gather [900, 1000] with nothing beside it.
    With ``overlap`` the all-reduce runs [400, 700], 200 ns of it beside
    the matmul; without, [600, 900], after it."""
    reduce_at = 400 if overlap else 600
    host = Plane("/host:CPU", [Line("python", [Ev("window", 0, 1000)])])
    devices = [Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [Ev("jit_train_step(3)", 0, 1000)]),
        Line("XLA Ops", [Ev("convolution.1", 0, 600),
                         Ev("all-reduce-start.2", reduce_at, 300),
                         Ev("all-gather.3", 900, 100)]),
    ]) for i in range(2)]
    return [host, *devices]


def tpu_step_trace():
    """Window [0, 1000] ns; on each of two devices one ``jit_train_step``
    run over [0, 1000]: the layer loop ``while.9`` over [0, 800] holds a
    matmul [0, 300], an asynchronous gather's start [300, 310], the matmul
    fusion that runs while the data moves [310, 700] and the gather's done
    [700, 800]; after the loop a synchronous all-gather [900, 1000] with
    nothing beside it."""
    host = Plane("/host:CPU", [Line("python", [Ev("window", 0, 1000)])])
    devices = [Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [Ev("jit_train_step(3)", 0, 1000)]),
        Line("XLA Ops", [Ev("while.9", 0, 800),
                         Ev("convolution.1", 0, 300),
                         Ev("async-collective-start.1", 300, 10),
                         Ev("fusion.4", 310, 390),
                         Ev("async-collective-done.1", 700, 100),
                         Ev("all-gather.3", 900, 100)]),
    ]) for i in range(2)]
    return [host, *devices]


def reading(trace):
    """What a reader of the four-chip cell gets, over a reduced trace."""
    class Run:
        chips = 4
        counts = {"batch": 8, "seq_len": 2048}
        model = run.load_json(run.ROOT / "chipbench/configs/granite-8b-l8.json")

    r = Run()
    r.trace = devtrace.reduce(trace)
    return r


def read(metric: str, r):
    return run.load_module(run.HERE / "metrics" / f"{metric}.py").read(r)


@pytest.mark.parametrize("overlap", [True, False])
def test_collective_shares(overlap):
    r = reading(step_trace(overlap))
    # all-reduce 300 ns and all-gather 100 ns of the step's 1000, whether
    # or not compute runs beside them
    assert read("collective_share.train", r) == pytest.approx(40.0)


def test_collective_share_counts_asynchronous_start_and_done():
    """The TPU's asynchronous gather counts by its start and done (10 +
    100 ns), beside the synchronous all-gather (100 ns); the matmul that
    ran while the data moved does not count."""
    r = reading(tpu_step_trace())
    assert read("collective_share.train", r) == pytest.approx(21.0)


def test_collective_shares_are_silent_without_collectives():
    trace = step_trace(True)
    for plane in trace[1:]:
        plane.lines[1].events = plane.lines[1].events[:1]
    r = reading(trace)
    assert read("collective_share.train", r) is None


@pytest.mark.parametrize("op, kind", [
    ("all-reduce-start.2", "all-reduce"), ("all-reduce.7", "all-reduce"),
    ("all-gather-done.3", "all-gather"), ("reduce-scatter.1", "reduce-scatter"),
    ("all-reduce-scatter-fusion.4", "reduce-scatter"), ("collective-permute.9",
                                                        "collective-permute"),
    ("all-to-all.2", "all-to-all"), ("async-collective-start", "async-collective"),
    ("async-collective-done.1", "async-collective"), ("fusion.12", None),
    ("convolution.1", None), ("while.9", None)])
def test_kind_of(op, kind):
    assert collectives.kind_of(op) == kind


@pytest.mark.parametrize("overlap, reduce_exposed", [(True, 100), (False, 300)])
def test_by_kind_under_each_scope(overlap, reduce_exposed):
    """Time, exposed time and self time of each kind, and both times by the
    scope of the op, mean over the two devices.  The all-reduce starts
    after the matmul, so by ``scopes``' rule its self time is all 300 ns
    whether or not the matmul hides part of it; its exposed time tells."""
    paths = {("jit_train_step", "convolution.1"): "jit(train_step)/mlp/dot_general",
             ("jit_train_step", "all-reduce-start.2"): "jit(train_step)/transpose(jvp(mlp))/dot",
             ("jit_train_step", "all-gather.3"): "jit(train_step)/optimizer/convert"}
    trace = step_trace(overlap)
    out = collectives.by_kind(trace, paths, scopes.op_table(trace, paths))
    ns = 1e-9
    assert out == {"jit_train_step": {
        "all-gather": {"ops": 1.0, "time_s": pytest.approx(100 * ns),
                       "exposed_s": pytest.approx(100 * ns), "self_s": pytest.approx(100 * ns),
                       "scopes": {"optimizer": [pytest.approx(100 * ns), pytest.approx(100 * ns)]}},
        "all-reduce": {"ops": 1.0, "time_s": pytest.approx(300 * ns),
                       "exposed_s": pytest.approx(reduce_exposed * ns),
                       "self_s": pytest.approx(300 * ns),
                       "scopes": {"mlp": [pytest.approx(300 * ns),
                                          pytest.approx(reduce_exposed * ns)]}},
    }}


def test_by_kind_leaves_the_loop_out_of_what_hides_a_collective():
    """The ``while`` spans the asynchronous gather's start and done, but it
    is no work that hides them: both are exposed, whole."""
    paths = {("jit_train_step", "async-collective-start.1"): "jit(train_step)/layer_stack/mlp/dot",
             ("jit_train_step", "async-collective-done.1"): "jit(train_step)/layer_stack/mlp/dot",
             ("jit_train_step", "all-gather.3"): "jit(train_step)/optimizer/convert"}
    trace = tpu_step_trace()
    out = collectives.by_kind(trace, paths, scopes.op_table(trace, paths))["jit_train_step"]
    ns = 1e-9
    assert out["async-collective"]["ops"] == 2.0
    for key in ("time_s", "exposed_s", "self_s"):
        assert out["async-collective"][key] == pytest.approx(110 * ns), key
        assert out["all-gather"][key] == pytest.approx(100 * ns), key
    assert out["async-collective"]["scopes"] == {"mlp": [pytest.approx(110 * ns),
                                                         pytest.approx(110 * ns)]}


def test_keeping_walks_the_planes_once():
    """The profiler hands ``scopes.op_table`` an iterator of planes that can
    be walked once; the table and the collectives both come from it."""
    paths = {("jit_train_step", "all-reduce-start.2"): "jit(train_step)/transpose(jvp(mlp))/dot"}
    reports: list = []
    table = collectives.keeping(reports)(iter(step_trace(False)), paths)
    assert table == scopes.op_table(step_trace(False), paths)
    assert reports == [collectives.by_kind(step_trace(False), paths, table)]
    assert reports[0]["jit_train_step"]["all-reduce"]["exposed_s"] == pytest.approx(300e-9)
