"""Local step (``core/client.make_batched_local_update``): device
milliseconds per traced round of the round-body program
(``jit_local_update`` on the trace), averaged over the chips."""
PROGRAM = "jit_local_update"


def read(run):
    t = run.trace
    sec = sum(d.module_seconds().get(PROGRAM, 0.0) for d in t.devices)
    if not sec:
        return None
    return 1e3 * sec / len(t.devices) / len(run.traced_rounds)
