"""Prefill's share of the chip's bf16 peak while it runs: the operations
one prefill requires (``flops.prefill_flops``), times the prefills in the
traced window, over their device time (the ``jit_prefill`` module
events) and the peak."""


def read(run):
    runs, seconds = run.trace.module("jit_prefill")
    if not runs or seconds <= 0:
        return None
    c = run.counts
    need = run.flops.prefill_flops(run.model, c["batch"], c["prompt_len"]) * runs
    return 100.0 * need / (seconds * run.peak["bf16_flops_per_s"])
