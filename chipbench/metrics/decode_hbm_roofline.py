"""Decode step's share of its roofline: the least time a step could take,
the larger of its required operations over the bf16 peak and its least
HBM bytes over the HBM bandwidth (``flops.decode_flops``,
``flops.decode_bytes``: the weights and the cache rows the sequences
hold), over the measured device time of a step (the ``jit_decode``
module events).  Steps run at every cache length of a batch in turn, so
the least time is averaged over those lengths."""


def read(run):
    runs, seconds = run.trace.module("jit_decode")
    if not runs or seconds <= 0:
        return None
    c = run.counts
    B, P, G = c["batch"], c["prompt_len"], c["gen_len"]
    peak, bw = run.peak["bf16_flops_per_s"], run.peak["hbm_bytes_per_s"]
    compute = memory = 0.0
    # decode step i feeds token i at position P + i: P + i + 1 rows held
    for i in range(G - 1):
        compute += run.flops.decode_flops(run.model, B, P + i + 1) / peak
        memory += run.flops.decode_bytes(run.model, B, P + i + 1) / bw
    least = max(compute, memory) / (G - 1)
    run.note(f"decode_hbm_roofline bound={'bytes' if memory >= compute else 'flops'} "
             f"least_step_s={least} measured_step_s={seconds / runs} "
             f"compute_s={compute / (G - 1)} memory_s={memory / (G - 1)}")
    return 100.0 * least / (seconds / runs)
