"""Whole decode step's share of the chip's bf16 peak: the operations a
decode step requires (``flops.decode_flops``, averaged over the cache
lengths of a batch) over the mean period of a decode step on the device
clock, from the start of one ``jit_decode`` run to the next within a
batch, which holds the step, the sampling and the device's idle time
while the host syncs (``devtrace.Reduced.period``)."""


def read(run):
    n, seconds = run.trace.period("jit_decode", breaks=("jit_prefill",))
    if not n or seconds <= 0:
        return None
    c = run.counts
    B, P, G = c["batch"], c["prompt_len"], c["gen_len"]
    need = sum(run.flops.decode_flops(run.model, B, P + i + 1) for i in range(G - 1)) / (G - 1)
    run.note(f"decode_mfu period_s={seconds / n} intervals={n}")
    return 100.0 * need / ((seconds / n) * run.peak["bf16_flops_per_s"])
