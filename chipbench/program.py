"""The system under test, built from a configuration file.

This is the one place that maps a configuration's published key names
onto the program's model harness.  Everything the configuration file does
not state (attention implementation, remat policy, compute type) comes
from the program's own configuration module, so a change there is what
the benchmark measures.
"""

from __future__ import annotations

import pathlib
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def harness(model: dict):
    """The program's harness for ``model``: its architecture module, with
    the depth and widths the configuration file states."""
    from repro.configs import load

    h = load(model["program"])
    return h.clone(
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        d_ff=model["intermediate_size"],
        vocab_size=model["vocab_size"],
    )


def named(fn, name: str):
    """``fn`` under ``name``, so that its compiled module is ``jit_<name>``
    in the trace whatever the program calls it."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call
