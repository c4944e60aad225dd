"""Kernels (``kernels/grouped_conv``): the forward kernel's calls in the
traced rounds, the sum of their least times over the sum of their device
durations, in percent (``bench.flops.grouped_conv``).  The backward runs
as XLA operations and is not in it."""
from bench.flops import grouped_conv, resnet


def read(run):
    calls = [k for k in run.trace.kernels() if grouped_conv.is_call(k)]
    expected = (len(run.traced_rounds) * run.steps
                * len(resnet.convs(run.cfg)) * len(run.trace.devices))
    share = grouped_conv.roofline(run.cfg, calls, expected, run.peaks)
    return None if share is None else 100.0 * share
