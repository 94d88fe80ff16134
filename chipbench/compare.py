"""The numbers that decide ``correct``, and the verdict.

Training: per tensor the norm of the first clipped gradient and of the
change over the compared steps, and per tensor the gap between the
program's norm and the reference's, over the reference's norm of that
tensor or of the median tensor, whichever is larger.  The change is
taken by the worst tensor.  The gradient is taken as the root mean
square of the tensors' gaps: its worst tensor is a different one on
each seed and swings by itself, so that on the chip the float8 control
read less than three times what sound runs read, where the root mean
square separates the two by four times.  Tensors whose reference
gradient is under a thousandth of the median tensor's move by round-off
alone and are left out of the change.  The loss of each step is
recorded beside them (``train_detail``) and not compared: at the cell's
size neither the control nor a planted fault reads it above what sound
runs read.

Serving: for every served token of the sampled requests, how far the
reference's logit of that token lies below the reference's best logit
at that position; the number is the widest such gap.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

MOVED = 1e-3  # a tensor moves when its reference gradient is over this share of the median's


@dataclass
class Outcome:
    metrics: dict              # end-to-end metric -> value
    attempted: int
    failed: int
    memory_peak_bytes: int
    numbers: dict              # compared number -> value
    counts: dict               # what the per-layer readers need
    variants: dict = field(default_factory=dict)   # calibration: variant -> numbers
    detail: dict = field(default_factory=dict)     # what the numbers were taken from


def leaf_gaps(got: dict, want: dict, keep=None) -> list[float]:
    """Each kept tensor's gap; a tensor the program lacks, or reads as
    not finite, reads infinite."""
    median = float(np.median([want[k] for k in want]))
    return [abs(got[k] - want[k]) / max(want[k], median)
            if k in got and math.isfinite(got[k]) else math.inf
            for k in want if keep is None or k in keep]


def train_numbers(got: dict, want: dict) -> dict:
    median = float(np.median(list(want["grad_norms"].values())))
    moved = {k for k, g in want["grad_norms"].items() if g >= MOVED * median}
    grad = np.asarray(leaf_gaps(got["grad_norms"], want["grad_norms"]))
    return {
        "grad_gap_rms": float(np.sqrt(np.mean(np.square(grad)))),
        "change_gap": max(leaf_gaps(got["change_norms"], want["change_norms"], moved)),
    }


def train_detail(got: dict, want: dict) -> dict:
    """Each step's loss and each tensor's norms, program beside reference,
    so that a number over its limit can be traced to its step or tensor."""
    return {"losses": [list(got["losses"]), list(want["losses"])],
            **{kind: {k: [got[kind].get(k), w] for k, w in want[kind].items()}
               for kind in ("grad_norms", "change_norms")}}


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position, the reference's best logit minus its logit of the
    served token; ``ref_logits`` (..., V), ``tokens`` (...)."""
    best = ref_logits.max(axis=-1)
    served = np.take_along_axis(ref_logits, tokens[..., None].astype(np.int64), axis=-1)[..., 0]
    return best - served


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit; a number with no limit fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not (value <= limit):
            ok = False
    return ok, checks
