"""Share of the training step's device time spent in collective ops, both
in the traced window and each the mean over the chips used: the union of
the intervals of the ops that name their collective (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute;
``devtrace.Reduced.collective_s``) and the time of the TPU compiler's
asynchronous ``async-collective-start`` and ``-done`` ops, over the
``jit_train_step`` module time.  A TPU core runs one op at a time, so the
two do not overlap, and what is counted is the time the step spends in
collectives rather than compute: the synchronous ones whole, the
asynchronous ones' issue and wait, and not the transfer that the compute
between their start and done hides.  Only the step runs in the window,
so every collective there is the step's."""

import re

ASYNC = re.compile(r"^jit_train_step/async-collective-(start|done)(\.\d+)?$")


def read(run):
    _, seconds = run.trace.module("jit_train_step")
    waits = sum(s for op, s in run.trace.ops.items() if ASYNC.match(op))
    if seconds <= 0 or run.trace.collective_s + waits <= 0:
        return None
    return 100.0 * (run.trace.collective_s + waits) / seconds
