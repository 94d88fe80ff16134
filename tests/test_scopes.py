"""Named scopes over the dense model step.

Every op of the train step, prefill and decode carries its
``jax.named_scope`` path as ``op_name`` metadata in the compiled HLO; a
profiler trace of the chip carries the same names, and the benchmark
attributes device time by them.  These tests compile the three programs
of the dense ``TransformerHarness`` at smoke width on the CPU and read
every instruction's ``op_name`` from ``compiled.as_text()``.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import load
from repro.models.api import ShapeCell
from repro.models.layers import Runtime
from repro.models.param import tree_abstract
from repro.optim import adamw
from repro.optim.compression import CompressionConfig
from repro.train.train_step import make_train_step

# a literal copy of the vocabulary the benchmark reads (chipbench/scopes.py):
# a rename on either side fails here
VOCABULARY = ("embed", "norm", "qkv", "kv_cache", "sdpa", "attn_out", "mlp",
              "layer_stack", "head", "loss", "optimizer")
LEAVES = tuple(s for s in VOCABULARY if s != "layer_stack")
RECOMPUTE = "rematted_computation"

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][a-z0-9_\-]*)\(")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


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


def scope_of(path: str) -> str:
    """The innermost vocabulary name of a ``;``-joined path's first name."""
    for part in reversed(components(path.split(";", 1)[0])):
        if part in VOCABULARY:
            return part
    return "unscoped"


@pytest.mark.parametrize("path, scope", [
    ("jit(train_step)/jvp(layer_stack)/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head"),
    ("jit(train_step)/transpose(jvp(loss))/mul;jit(train_step)/transpose(jvp(norm))/mul",
     "loss"),
], ids=["jvp", "transpose-jvp", "joined"])
def test_scope_of_reads_through_wrappers(path, scope):
    assert scope_of(path) == scope


def instructions(hlo: str) -> list[dict]:
    """Every instruction of the module: its computation, opcode, op_name
    and, for a fusion, the computation it calls."""
    out, comp = [], None
    for line in hlo.splitlines():
        c = _COMP.match(line)
        if c and " = " not in line:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        out.append({"comp": comp, "name": m.group(1), "opcode": m.group(2),
                    "op_name": op.group(1) if op else "",
                    "calls": calls.group(1) if calls else None})
    return out


def holds_dot(instrs: list[dict]) -> set[str]:
    """Computations that hold a dot or a convolution, through nested calls."""
    direct = {i["comp"] for i in instrs if i["opcode"] in ("dot", "convolution")}
    held = set(direct)
    grew = True
    while grew:
        grew = False
        for i in instrs:
            if i["calls"] in held and i["comp"] not in held:
                held.add(i["comp"])
                grew = True
    return held


@pytest.fixture(scope="module")
def programs() -> dict[str, list[dict]]:
    h = load("granite_3_2b", smoke=True)
    rt = Runtime()
    params = tree_abstract(h.param_specs(), dtype=jnp.bfloat16)
    opt = jax.eval_shape(adamw.init_opt_state, params)
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    cache = tree_abstract(h.serve_state_specs(ShapeCell("s", "decode", 40, 2)))
    step = make_train_step(h.loss(rt), adamw.OptConfig(), CompressionConfig())
    compiled = {
        "train_step": jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt, {"tokens": tokens, "labels": tokens}).compile(),
        "prefill": jax.jit(h.prefill(rt), donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((2, 16), jnp.int32)).compile(),
        "decode": jax.jit(h.decode(rt), donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile(),
    }
    return {k: instructions(c.as_text()) for k, c in compiled.items()}


EXPECTED = {
    "train_step": {"sdpa", "head", "loss", "optimizer"},
    "prefill": {"sdpa", "kv_cache"},
    "decode": {"sdpa", "kv_cache"},
}


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_every_op_lands_in_one_leaf_scope(programs, program):
    instrs = programs[program]
    assert instrs, "no instruction parsed from the compiled module"
    with_dot = holds_dot(instrs)

    matmuls = [i for i in instrs if i["opcode"] in ("dot", "convolution")
               or (i["opcode"] == "fusion" and i["calls"] in with_dot)]
    assert matmuls
    for i in matmuls:
        assert scope_of(i["op_name"]) in LEAVES, (i["name"], i["op_name"])

    scopes = {scope_of(i["op_name"]) for i in instrs}
    assert EXPECTED[program] <= scopes, EXPECTED[program] - scopes
    if program == "train_step":
        assert any(RECOMPUTE in components(i["op_name"]) for i in instrs)

    for i in instrs:
        for name in i["op_name"].split(";"):
            leaves = {c for c in components(name) if c in LEAVES}
            assert len(leaves) <= 1, (i["name"], name)
