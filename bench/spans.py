"""Host spans around calls into the program's layers, and its compiles.

The program has no spans of its own yet, so the harness puts them around
the calls it can reach from outside: a wrapped method or module function
records its host time (``time.perf_counter``) per round and also enters a
``jax.profiler.TraceAnnotation`` of the same name, which a traced run
finds on the device trace's clock.
"""
from __future__ import annotations

import collections
import contextlib
import time

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Spans:
    """Host seconds per (span name, round)."""

    def __init__(self):
        self.round = 1
        self.seconds: dict = collections.defaultdict(float)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.seconds[(name, self.round)] += time.perf_counter() - t0

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def per_round(self, name: str, rounds) -> list[float]:
        return [self.seconds.get((name, r), 0.0) for r in rounds]


@contextlib.contextmanager
def patched(obj, name: str, value):
    """Set ``obj.name`` to ``value`` for the duration of the block."""
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, orig)


class Compiles:
    """XLA backend compiles and persistent-cache loads, as
    ``jax.monitoring`` reports them.  jax times a program's compile or
    load from the persistent cache as one ``backend_compile_duration``
    event either way, and counts the loads apart as cache hits."""

    def __init__(self):
        self.count = 0          # compiles and loads
        self.loads = 0

    @property
    def compiles(self) -> int:
        return self.count - self.loads

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE:
            self.count += 1

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self.loads += 1

    def register(self) -> "Compiles":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self
