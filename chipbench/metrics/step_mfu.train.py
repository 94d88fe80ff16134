"""Whole training step's share of the chip's bf16 peak: the forward and
backward operations a step requires (``flops.train_step_flops``), times
the steps in the traced window, over their device time (the
``jit_train_step`` module events, averaged over the chips used) and the
peak of every chip used."""


def read(run):
    runs, seconds = run.trace.module("jit_train_step")
    if not runs or seconds <= 0:
        return None
    c = run.counts
    need = run.flops.train_step_flops(run.model, c["batch"], c["seq_len"]) * runs
    return 100.0 * need / (seconds * run.chips * run.peak["bf16_flops_per_s"])
