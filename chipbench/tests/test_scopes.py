"""Self time by named scope, on small traces with hand-counted answers."""

from dataclasses import dataclass, field

import numpy as np
import pytest

import devtrace
import scopes

NS = 1e-9
WHILE = "jit(train_step)/transpose(jvp(layer_stack))/while"
BODY = WHILE + "/body/closed_call/checkpoint"


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


def nested():
    """Window [0, 1000] ns.  ``jit_train_step`` [50, 900]: ``while.1``
    [100, 600] holds two body ops, [150, 300] and [320, 500]; then
    ``copy.4`` [700, 800], which the HLO map does not name, and
    ``fusion.5`` [820, 850]."""
    host = Plane("/host:CPU", [Line("python", [Ev("window", 0, 1000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_train_step(7)", 50, 850)]),
        Line("XLA Ops", [
            Ev("%while.1 = (s32[]) while(%tuple.1)", 100, 500),
            Ev("fusion.2", 150, 150),
            Ev("fusion.3", 320, 180),
            Ev("copy.4", 700, 100),
            Ev("fusion.5", 820, 30)]),
    ])
    hlo = {("jit_train_step", "while.1"): WHILE,
           ("jit_train_step", "fusion.2"): BODY + "/sdpa/dot_general",
           ("jit_train_step", "fusion.3"): BODY + "/rematted_computation/mlp/dot_general",
           ("jit_train_step", "fusion.5"): "jit(train_step)/transpose(jvp(head))/dot_general"}
    return [host, dev], hlo


def test_self_times_split_the_busy_time():
    planes, hlo = nested()
    table = scopes.op_table(planes, hlo)
    own = {op: row[0] for (_, op), row in table.items()}
    assert own == pytest.approx({"while.1": (500 - 150 - 180) * NS, "fusion.2": 150 * NS,
                                 "fusion.3": 180 * NS, "copy.4": 100 * NS,
                                 "fusion.5": 30 * NS})
    r = devtrace.reduce(planes)
    assert sum(own.values()) == pytest.approx(r.busy_s)
    # the op totals count the while and its body both
    assert r.ops["jit_train_step/while.1"] == pytest.approx(500 * NS)
    paths = {op: scopes.scope_of(row[2]) for (_, op), row in table.items()}
    assert paths == {"while.1": "layer_stack", "fusion.2": "sdpa", "fusion.3": "mlp",
                     "copy.4": "unscoped", "fusion.5": "head"}
    busy = 630.0
    shares = scopes.report(table)["shares"]
    assert shares == pytest.approx({
        "sdpa_share.train": 100 * 150 / busy,
        "head_loss_share.train": 100 * 30 / busy,
        "recompute_share.train": 100 * 180 / busy,
        "unscoped_share.train": 100 * 100 / busy,
    })


def test_self_times_are_clipped_to_the_window():
    planes, hlo = nested()
    planes[0].lines[0].events[0] = Ev("window", 200, 300)     # [200, 500]
    table = scopes.op_table(planes, hlo)
    own = {op: row[0] for (_, op), row in table.items()}
    assert own == pytest.approx({"while.1": 20 * NS, "fusion.2": 100 * NS,
                                 "fusion.3": 180 * NS})
    assert sum(own.values()) == pytest.approx(devtrace.reduce(planes).busy_s)


@pytest.mark.parametrize("path, scope", [
    (BODY + "/rematted_computation/qkv/mul", "qkv"),
    ("jit(train_step)/transpose(jvp(loss))/mul", "loss"),
    ("jit(decode)/layer_stack/while/body/closed_call/kv_cache/dynamic_update_slice", "kv_cache"),
    ("jit(train_step)/optimizer/jit(norm)/sqrt", "optimizer"),
    ("jit(train_step)/transpose(jvp(loss))/mul;jit(train_step)/jvp(head)/dot_general", "loss"),
    ("jit(decode)/jit(main)/while/body/dynamic_slice", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(path, scope):
    assert scopes.scope_of(path) == scope


def test_recomputed():
    assert scopes.recomputed(BODY + "/rematted_computation/mlp/dot_general")
    assert not scopes.recomputed(BODY + "/mlp/dot_general")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_self_time_adds_up_to_the_union(seed):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, 1000, 300).astype(float)
    e = s + rng.integers(1, 200, 300)
    own = scopes.self_time(s, e)
    us, ue = devtrace.union(s, e)
    assert (own >= 0).all()
    assert own.sum() == pytest.approx(float(np.sum(ue - us)))


def test_share_is_none_without_scopes():
    table = {("jit_decode", "copy.1"): [1.0, 1, ""],
             ("jit_decode", "fusion.2"): [2.0, 1, "jit(decode)/jit(main)/dot_general"]}
    assert scopes.share(table, ("jit_decode",), lambda p: True) is None
    assert scopes.share(table, ("jit_prefill",), lambda p: True) is None
    assert scopes.report(table)["shares"] == {}


def test_hlo_op_paths_reads_a_real_trace(tmp_path):
    """The optimised HLO in the ``/host:metadata`` plane of a trace taken
    on the CPU names each op by its scope."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("loss"):
            return jnp.sum(y * y)

    g = jax.jit(jax.grad(f))
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(devtrace.find_xplane(str(tmp_path)), "rb") as fh:
        names = scopes.hlo_op_paths(fh.read())
    found = {scopes.scope_of(path) for (module, _), path in names.items() if module == "jit_f"}
    assert {"mlp", "loss"} <= found
