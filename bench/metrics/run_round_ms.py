"""Executor (``core/executor`` ``run_round``): host milliseconds per
window round inside the executor's ``run_round``, the median over the
window's rounds."""
import statistics


def read(run):
    per = run.spans.per_round("bench.run_round", run.window_rounds)
    return 1e3 * statistics.median(per) if per else None
