"""Teacher precompute (``Algorithm.precompute_aux``, the ``jit_stage``
program, a ``lax.map`` over chunks of rows): the forward FLOPs of the
teacher over the real rows of every sampled shard in the traced rounds,
over the device seconds of ``jit_stage`` in those rounds, summed over the
chips, x peak FLOP/s, in percent.  Rows the stage pads on are not counted: padding is
waste."""
from bench.flops import resnet

PROGRAM = "jit_stage"


def read(run):
    t = run.trace
    sec = sum(d.module_seconds().get(PROGRAM, 0.0) for d in t.devices)
    if not sec:
        return None
    rows = sum(run.sizes[int(c)] for r in run.traced_rounds
               for c in run.cohorts[r])
    flops = resnet.forward_flops(run.cfg) * rows
    return 100.0 * flops / (sec * run.peaks["flops_per_s"])
