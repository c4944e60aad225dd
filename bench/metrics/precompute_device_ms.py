"""Teacher precompute (``Algorithm.precompute_aux``): device milliseconds
per traced round of the precompute program, which the executors jit as
``stage`` (``jit_stage`` on the trace), averaged over the chips."""
PROGRAM = "jit_stage"


def read(run):
    t = run.trace
    sec = sum(d.module_seconds().get(PROGRAM, 0.0) for d in t.devices)
    if not sec:
        return None
    return 1e3 * sec / len(t.devices) / len(run.traced_rounds)
