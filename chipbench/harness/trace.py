"""Reading a profiler trace: device busy time, program and kernel time, and
what the host was doing in each idle gap.

A traced run writes JAX's profile (``.xplane.pb``) to a scratch directory.
``load`` keeps from it the device planes' operation and program events and
the harness's own host spans (``chipbench.*``), all on the profiler's one
clock in nanoseconds.  Everything after ``load`` is plain arithmetic on
those events, checked in the tests on a small recorded trace.
"""
from __future__ import annotations

import glob
import math
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # device -> [Event]
    modules: dict = field(default_factory=dict)   # device -> [Event]
    spans: list = field(default_factory=list)     # [Event], host

    @classmethod
    def from_json(cls, d: dict) -> Trace:
        ev = lambda xs: [Event(**x) for x in xs]
        return cls({k: ev(v) for k, v in d["ops"].items()},
                   {k: ev(v) for k, v in d["modules"].items()},
                   ev(d["spans"]))

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def window(self, span_name: str) -> tuple[float, float]:
        """The traced window: from the start of the first span named
        ``span_name`` to the end of the last, widened to every device
        operation.  A run traces only its spans and what they ran, and the
        profile places device operations up to a few ms earlier than the
        host spans that launched them."""
        s = [e for e in self.spans if e.name == span_name]
        if not s:
            raise ValueError(f"no {span_name!r} spans in the trace")
        ops = [e for d in self.devices for e in self.ops[d]]
        return (min(e.start for e in s + ops), max(e.end for e in s + ops))


def load(directory: str) -> Trace:
    """The trace that ``jax.profiler`` wrote under ``directory``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {paths}")
    out = Trace()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.split(":")[-1]
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [Event(e.name, e.start_ns, e.end_ns)
                           for e in line.events]
                    (out.ops if line.name == OPS_LINE
                     else out.modules)[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans += [Event(e.name, e.start_ns, e.end_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
    out.spans.sort(key=lambda e: e.start)
    return out


def union(events, t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged intervals covered by ``events``, clipped to [t0, t1]."""
    iv = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                if e.end > t0 and e.start < t1)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals, t0: float, t1: float) -> float:
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in intervals)


def busy_ns(trace: Trace, t0: float, t1: float) -> float:
    """Time in [t0, t1] in which an operation ran, averaged over devices."""
    devs = trace.devices
    return sum(covered(union(trace.ops[d], t0, t1), t0, t1)
               for d in devs) / len(devs)


def in_window(events, t0: float, t1: float) -> list[Event]:
    """Events that start inside [t0, t1)."""
    return [e for e in events if t0 <= e.start < t1]


def matching(events, needle: str) -> list[Event]:
    return [e for e in events if needle in e.name]


MOSAIC = 'custom_call_target="tpu_custom_call"'
_RESULT = re.compile(r"^%\S+ = \w+\[([\d,]*)\]")


def mosaic_calls(events, elements: int, operands: int) -> list[Event]:
    """Pallas (Mosaic) kernel calls whose result holds ``elements`` values
    and that take ``operands`` operands.  A kernel's operation name is not
    stable across programs, so a call is known by its signature."""
    out = []
    for e in events:
        if MOSAIC not in e.name:
            continue
        m = _RESULT.match(e.name)
        args = e.name.partition("custom-call(")[2].partition(
            "), custom_call_target")[0]
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) \
                == elements and args.count("%") == operands:
            out.append(e)
    return out


def leaves(events) -> list[Event]:
    """The events that contain no other event: the XLA Ops line nests a
    loop's body operations inside the loop's own event."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def op_totals(trace: Trace, t0: float, t1: float, top: int = 10):
    """[name, seconds] of the innermost device operations that took most
    time in the window, averaged over devices."""
    tot: dict[str, float] = {}
    for d in trace.devices:
        for e in leaves(trace.ops[d]):
            if e.end > t0 and e.start < t1:
                tot[e.name] = tot.get(e.name, 0.0) + (
                    min(e.end, t1) - max(e.start, t0))
    n = len(trace.devices)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n / 1e9] for k, v in ranked]


def idle_gaps(trace: Trace, t0: float, t1: float, top: int = 10):
    """[host span, seconds] of the longest device idle gaps in the window
    (device 0), each named by the innermost harness span around its
    midpoint, or "no span" when the host was outside every span."""
    busy = union(trace.ops[trace.devices[0]], t0, t1)
    gaps, last = [], t0
    for a, b in busy:
        if a > last:
            gaps.append((last, a))
        last = b
    if t1 > last:
        gaps.append((last, t1))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        around = [s for s in trace.spans if s.start <= mid < s.end]
        name = min(around, key=lambda s: s.dur).name if around else "no span"
        out.append([name, (b - a) / 1e9])
    return out

