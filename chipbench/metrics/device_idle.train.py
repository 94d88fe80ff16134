"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
chips used (``devtrace.Reduced.idle_percent``)."""


def read(run):
    return run.trace.idle_percent()
