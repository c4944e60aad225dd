"""Kernels (``kernels/kd_kl``): the forward and backward kernel calls in
the traced rounds, the sum of their least times at the true rows (the
cohort's batch on each chip) and classes over the sum of their device
durations, in percent (``bench.flops.kd_kl``).  One forward and one
backward call per local step is expected; otherwise nothing is read."""
from bench import traffic
from bench.flops import kd_kl


def read(run):
    kernels = run.trace.kernels()
    fwd = [k for k in kernels if kd_kl.is_forward(k)]
    bwd = [k for k in kernels if kd_kl.is_backward(k)]
    expected = len(run.traced_rounds) * run.steps * len(run.trace.devices)
    if not fwd or len(fwd) != expected or len(bwd) != expected:
        return None
    rows = (traffic.cohort_size(run.cfg) // len(run.trace.devices)
            * run.cfg["batch_size"])
    classes = run.cfg["num_classes"]
    least = expected * (
        kd_kl.least_seconds(rows, classes, False, run.peaks)
        + kd_kl.least_seconds(rows, classes, True, run.peaks))
    return 100.0 * least / sum(k.seconds for k in fwd + bwd)
