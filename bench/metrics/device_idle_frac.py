"""Device: the share of an untraced round in which the device runs no
operation, in percent, averaged over the chips: 1 - (the union of the
device's op intervals per traced round) / (the window's mean round time).
The profiler lengthens the traced rounds themselves (each dispatch costs
more while it records), so their own wall time is not the denominator."""


def read(run):
    busy = run.trace.busy_s() / len(run.traced_rounds)
    return 100.0 * (1.0 - busy / (run.window_s / len(run.window_rounds)))
