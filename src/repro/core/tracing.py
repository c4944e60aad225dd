"""Spans and counters inside the FL round, off by default.

The round loop, the executors and the server mark where their work
happens::

    with tracing.span("fl.exec.stack"):
        ...
    tracing.count("host_syncs")

With no recorder active (the default) ``span`` returns one shared null
context after a single module-level check and ``count`` returns at once,
so the marks cost a function call each.  ``enable()`` makes a
``Recorder`` active.  Then every span

* enters ``jax.profiler.TraceAnnotation(name, round=<r>)``, so a profile
  taken meanwhile shows it on the same clock as the device's operations
  (the round itself is a ``StepTraceAnnotation("fl.round", step_num=r)``);
* is kept in memory as ``(name, round, parent, t0, t1)`` on
  ``time.perf_counter``'s clock, its parent being the innermost span open
  when it started.

Counters add up per ``(name, round)``.  The round is the number of the
innermost open ``round_span``, 0 outside any round.  ``enable()`` also
registers, once per process, a ``jax.monitoring`` duration listener that
charges every trace (``jaxpr_trace_duration``), lowering
(``jaxpr_to_mlir_module_duration``) and compile or persistent-cache load
(``backend_compile_duration``) to the current round and to the jitted
function jax names in its ``fun_name``.

Nothing is written out: a caller reads the recorder in-process.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Iterable, Optional

import jax

ROUND = "fl.round"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_KINDS = {TRACE_EVENT: "traces", LOWER_EVENT: "lowerings",
          COMPILE_EVENT: "compiles"}

_NULL = contextlib.nullcontext()
_active: Optional["Recorder"] = None
_listening = False


@dataclasses.dataclass
class Span:
    name: str
    round: int
    parent: Optional[int]       # index of the enclosing span, or None
    t0: float
    t1: float = float("nan")
    child_s: float = 0.0        # seconds covered by direct children

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Recorder:
    """Spans, counters and compile events of one process, by round."""

    def __init__(self):
        self.spans: list[Span] = []
        self.counters: dict = collections.defaultdict(float)
        # (function, round) -> {"traces", "lowerings", "compiles", "seconds"}
        self.functions: dict = {}
        # round -> [(t0, t1)] of every trace, lowering and compile event
        self.compile_spans: dict = collections.defaultdict(list)
        self.round = 0
        self._open: list[int] = []

    # -- recording ---------------------------------------------------------
    def span(self, name: str):
        return self._record(name, jax.profiler.TraceAnnotation(
            name, round=self.round))

    @contextlib.contextmanager
    def _record(self, name: str, annotation):
        parent = self._open[-1] if self._open else None
        idx = len(self.spans)
        rec = Span(name, self.round, parent, time.perf_counter())
        self.spans.append(rec)
        self._open.append(idx)
        try:
            with annotation:
                yield rec
        finally:
            rec.t1 = time.perf_counter()
            self._open.pop()
            if parent is not None:
                self.spans[parent].child_s += rec.seconds

    @contextlib.contextmanager
    def round_span(self, r: int):
        outer, self.round = self.round, r
        try:
            with self._record(ROUND, jax.profiler.StepTraceAnnotation(
                    ROUND, step_num=r)):
                yield
        finally:
            self.round = outer

    def count(self, name: str, n: float = 1) -> None:
        self.counters[(name, self.round)] += n

    def on_duration(self, event: str, duration: float, **kw) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        now = time.perf_counter()
        fun = str(kw.get("fun_name", "?"))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]             # lowering and compile: "jit(<name>)"
        tally = self.functions.setdefault(
            (fun, self.round),
            {"traces": 0, "lowerings": 0, "compiles": 0, "seconds": 0.0})
        tally[kind] += 1
        tally["seconds"] += duration
        self.compile_spans[self.round].append((now - duration, now))

    # -- reading -----------------------------------------------------------
    def per_round(self, name: str, rounds: Iterable[int]) -> list[float]:
        """Seconds inside spans called ``name``, per round."""
        return self._by_round(name, rounds, lambda s: s.seconds)

    def self_seconds(self, name: str, rounds: Iterable[int]) -> list[float]:
        """Seconds inside spans called ``name`` less the part their child
        spans cover, per round."""
        return self._by_round(name, rounds, lambda s: s.seconds - s.child_s)

    def _by_round(self, name, rounds, value) -> list[float]:
        total: dict = collections.defaultdict(float)
        for s in self.spans:
            if s.name == name:
                total[s.round] += value(s)
        return [total.get(r, 0.0) for r in rounds]

    def counter(self, name: str, rounds: Iterable[int]) -> list[float]:
        return [self.counters.get((name, r), 0) for r in rounds]

    def compiles_by_function(self, rounds: Optional[Iterable[int]] = None
                             ) -> dict:
        """Per jitted function (as jax names its trace): how many times
        it was traced, lowered, and compiled or loaded from the
        persistent cache, and the seconds of all three, in ``rounds``
        (every round, and outside any, when None)."""
        keep = None if rounds is None else set(rounds)
        out: dict = {}
        for (fun, r), tally in self.functions.items():
            if keep is None or r in keep:
                acc = out.setdefault(fun, dict.fromkeys(tally, 0))
                for k, v in tally.items():
                    acc[k] += v
        return out

    def compile_seconds(self, rounds: Iterable[int]) -> list[float]:
        """Host seconds per round spent tracing, lowering and compiling or
        loading: the union of the events' intervals, so that a jitted
        function traced inside another's trace counts once."""
        out = []
        for r in rounds:
            total, end = 0.0, float("-inf")
            for t0, t1 in sorted(self.compile_spans.get(r, ())):
                if t1 > end:
                    total += t1 - max(t0, end)
                    end = t1
            out.append(total)
        return out


def _forward(event: str, duration: float, **kw) -> None:
    if _active is not None:
        _active.on_duration(event, duration, **kw)


def enable() -> Recorder:
    """Make a new ``Recorder`` active and return it."""
    global _active, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_forward)
        _listening = True
    _active = Recorder()
    return _active


def disable() -> None:
    """Stop recording."""
    global _active
    _active = None


def span(name: str):
    """A context marking ``name`` in the current round."""
    rec = _active
    if rec is None:
        return _NULL
    return rec.span(name)


def round_span(r: int):
    """The context of round ``r``: spans, counters and compiles inside it
    are charged to ``r``."""
    rec = _active
    if rec is None:
        return _NULL
    return rec.round_span(r)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` in the current round."""
    rec = _active
    if rec is not None:
        rec.count(name, n)
