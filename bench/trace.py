"""From a profiler trace to what the per-layer metrics read.

``reduce(path, chips)`` reads one ``.xplane.pb`` with ``jax.profiler.
ProfileData`` and keeps, inside the traced window (the first start to the
last end of the harness's ``bench.round`` host spans):

* per device (``/device:TPU:<i>`` for the chips in use), the ``XLA Ops``
  events (HLO instruction text, start, end) and the ``XLA Modules`` events
  (one per program execution, ``jit_<function>(<hash>)``);
* the harness's host spans, every event whose name starts with ``bench.``.

From these it derives the busy time (the union of the op intervals, so a
``while`` op covers its body), the idle gaps labelled by the innermost
host span around each, and the Pallas kernel calls
(``custom_call_target="tpu_custom_call"``) with their operand and result
shapes parsed from the HLO text.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

SPAN_PREFIX = "bench."
ROUND_SPAN = "bench.round"
SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
MODULE_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Kernel:
    """One Pallas kernel call: operand and result shapes, device time."""
    operands: list[tuple]
    results: list[tuple]
    seconds: float


@dataclasses.dataclass
class Device:
    ops: list[tuple]          # (start_ns, end_ns, hlo text)
    modules: list[tuple]      # (start_ns, end_ns, module name without hash)

    def busy_intervals(self) -> list[tuple]:
        merged: list[list] = []
        for s, e, _ in sorted(self.ops):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [tuple(m) for m in merged]

    def busy_ns(self) -> float:
        return float(sum(e - s for s, e in self.busy_intervals()))

    def module_seconds(self) -> dict:
        out: dict = collections.defaultdict(float)
        for s, e, name in self.modules:
            out[name] += (e - s) * 1e-9
        return dict(out)

    def module_of(self, t: float) -> str:
        if not hasattr(self, "_starts"):
            self._starts = [m[0] for m in self.modules]
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and self.modules[i][1] >= t:
            return self.modules[i][2]
        return "?"


@dataclasses.dataclass
class Trace:
    window: tuple             # (start_ns, end_ns)
    devices: list[Device]
    spans: list[tuple]        # (start_ns, end_ns, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def rounds(self) -> int:
        return sum(1 for s in self.spans if s[2] == ROUND_SPAN)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in use."""
        return sum(d.busy_ns() for d in self.devices) * 1e-9 / len(
            self.devices)

    def idle_gaps(self, device: int = 0) -> list[tuple]:
        """(seconds, label) of every idle gap of one device, the label
        being the innermost host span around the gap's middle."""
        busy = self.devices[device].busy_intervals()
        edges = [self.window[0]] + [t for iv in busy for t in iv] + [
            self.window[1]]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                out.append(((e - s) * 1e-9, self.label((s + e) / 2)))
        return out

    def label(self, t: float) -> str:
        inside = [sp for sp in self.spans if sp[0] <= t <= sp[1]]
        if not inside:
            return "no host span"
        return min(inside, key=lambda sp: sp[1] - sp[0])[2]

    def top_ops(self, n: int = 10) -> list[list]:
        """Device time by program and HLO instruction, largest first,
        summed over the devices in use."""
        total: dict = collections.defaultdict(float)
        for d in self.devices:
            for s, e, text in d.ops:
                total[f"{d.module_of(s)}:{text.split(' = ')[0]}"] += (
                    e - s) * 1e-9
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def kernels(self) -> list[Kernel]:
        out = []
        for d in self.devices:
            for s, e, text in d.ops:
                if 'custom_call_target="tpu_custom_call"' in text:
                    out.append(parse_kernel(text, (e - s) * 1e-9))
        return out


def parse_kernel(text: str, seconds: float) -> Kernel:
    """Shapes of one custom-call's HLO text: ``%x = <results>
    custom-call(<operands>), ...``."""
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), custom_call_target=")[0]
    shapes = lambda s: [tuple(int(d) for d in m.group(1).split(",") if d)
                        for m in SHAPE.finditer(s)]
    return Kernel(shapes(operands), shapes(head.partition(" = ")[2]),
                  seconds)


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), name) for s, e, name in events
            if e > lo and s < hi]


def reduce(path: str, chips: int) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, raw = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if idx >= chips:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            get = lambda name, strip=False: [
                (e.start_ns, e.start_ns + e.duration_ns,
                 MODULE_HASH.sub("", e.name) if strip else e.name)
                for e in (lines[name].events if name in lines else [])]
            raw[idx] = (get("XLA Ops"), get("XLA Modules", strip=True))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    rounds = [s for s in spans if s[2] == ROUND_SPAN]
    if not rounds or len(raw) < chips:
        raise ValueError(f"trace {path}: {len(rounds)} round spans, "
                         f"devices {sorted(raw)} of {chips}")
    lo, hi = min(s[0] for s in rounds), max(s[1] for s in rounds)
    devices = [Device(_clip(raw[i][0], lo, hi),
                      sorted(_clip(raw[i][1], lo, hi)))
               for i in sorted(raw)]
    return Trace((lo, hi), devices, sorted(_clip(spans, lo, hi)))
