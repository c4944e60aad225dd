"""Round loop (``core/fl_loop`` + ``core/server``): host milliseconds per
window round in ``algo.server_update`` and ``fl_loop.evaluate``, the
median over the window's rounds."""
import statistics


def read(run):
    per = [a + b for a, b in zip(
        run.spans.per_round("bench.server_update", run.window_rounds),
        run.spans.per_round("bench.evaluate", run.window_rounds))]
    return 1e3 * statistics.median(per) if per else None
