"""The client-batched convolution kernel (``kernels/grouped_conv``):
operations and bytes of one forward call, and how its calls in a trace
are matched to the model's convolutions.

A call convolves ``clients`` stacked clients' ``batch`` examples with
each client's own filters.  Its operations and bytes are the
convolution's own (``bench.flops.resnet.Layer``): the true input
channels, no lane padding, no wrap-around columns, taps on the SAME
padding not counted.  A call in a trace shows padded shapes:
``x (clients, batch, stride**2, rows, Cin_pad)`` and
``w (clients, k, k, Cin_pad, Cout_pad)``; what identifies its layer
without the padding is the kernel size and the stride.
"""
from __future__ import annotations

import collections

from bench.flops import resnet


def is_call(kernel) -> bool:
    ops = kernel.operands
    return len(ops) == 2 and len(ops[0]) == 5 and len(ops[1]) == 5


def signature(kernel) -> tuple:
    """(kernel size, stride) of a traced call."""
    x, w = kernel.operands
    return w[1], int(round(x[2] ** 0.5))


def least_seconds(layer: resnet.Layer, clients: int, batch: int,
                  peaks: dict) -> float:
    """max(operations / peak FLOP/s, bytes / peak HBM bandwidth)."""
    return max(layer.flops(clients * batch) / peaks["flops_per_s"],
               layer.bytes(batch, clients) / peaks["hbm_bytes_per_s"])


def roofline(cfg: dict, calls: list, calls_expected: int,
             peaks: dict) -> float | None:
    """Sum of the calls' least times over the sum of their durations, or
    None where the calls cannot be matched to the model's convolutions:
    each forward calls every convolution once, so a (kernel size, stride)
    group of n convolutions must hold a multiple of n calls, and all the
    groups ``calls_expected`` calls."""
    if not calls or len(calls) != calls_expected:
        return None
    layers = collections.defaultdict(list)
    for layer in resnet.convs(cfg):
        layers[(layer.k, layer.stride)].append(layer)
    seen = collections.defaultdict(list)
    for call in calls:
        seen[signature(call)].append(call)
    if set(seen) != set(layers):
        return None
    least = 0.0
    for sig, group in seen.items():
        if len(group) % len(layers[sig]):
            return None
        clients, batch = group[0].operands[0][:2]
        least += len(group) / len(layers[sig]) * sum(
            least_seconds(layer, clients, batch, peaks)
            for layer in layers[sig])
    return least / sum(c.seconds for c in calls)
