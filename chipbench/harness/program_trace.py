"""The program's own spans and scopes in a profiler trace, and the clock
that lays the host's spans over the device's operations.

``trace.load`` keeps the harness's spans and the device's operations.  This
module reads the same ``.xplane.pb`` for what the program itself records:

* host spans named ``repro.*`` (``jax.profiler.TraceAnnotation`` in
  ``src/``), with their arguments, such as the row counts the serving
  engine puts on ``repro.serve.prefill`` and ``repro.serve.unpack``;
* for each device operation, its name stack (the HLO ``op_name``, which
  ``jax.named_scope`` extends), kept by a v5e profile as the ``tf_op``
  stat of the operation's event metadata.  ``jax.profiler.ProfileData``
  does not expose event metadata, so ``op_scopes`` reads those few fields
  of the protobuf itself.

Host and device timestamps are on clocks that disagree by an offset
``delta`` (host time = device time + delta).  ``offset_bounds`` bounds it
from programs the host is known to have launched, and waited for, inside
given spans; ``delta`` takes the lower bound.
"""
from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass, field

from harness import trace as T

PREFIX = "repro."
SCOPE_STAT = "tf_op"
DISPATCH, WAIT = "repro.serve.decode_dispatch", "repro.serve.decode_wait"
PREFILL = "repro.serve.prefill"
DECODE_PROGRAM, PREFILL_PROGRAM = "_fused_steps", "_prefill_chunk"
TRAIN_PROGRAM = "train_step"
TRAIN_SPAN = "chipbench.train_step"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


@dataclass(frozen=True)
class Span:
    name: str
    start: float        # ns, host clock
    end: float
    args: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class ProgramTrace:
    spans: list = field(default_factory=list)     # [Span], by start
    scopes: dict = field(default_factory=dict)    # device -> {op name: path}
    delta: float | None = None                    # ns; None: not known

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


# -- the protobuf, as far as op scopes need it -------------------------------
# XSpace.planes = 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5
# (maps: key = 1, value = 2); XEventMetadata.name = 2, stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, str_value = 5,
# ref_value = 7 (an interned string: the name of a stat metadata entry).
def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message; a length-delimited value is a
    memoryview, a varint an int, fixed-width values raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_values(b):
    for f, v in _fields(b):
        if f == 2:
            yield v


def _text(v) -> str:
    return bytes(v).decode("utf-8", errors="replace")


def _plane_scopes(plane) -> tuple[str, dict]:
    """(plane name, {event metadata name: ``tf_op`` value})."""
    name, stat_names, metas = "", {}, []
    for f, v in _fields(plane):
        if f == 2:
            name = _text(v)
        elif f == 4:
            metas += list(_map_values(v))
        elif f == 5:
            for m in _map_values(v):
                d = dict(_fields(m))
                stat_names[d.get(1, 0)] = _text(d.get(2, b""))
    want = [k for k, n in stat_names.items() if n == SCOPE_STAT]
    out: dict[str, str] = {}
    if not want:
        return name, out
    for m in metas:
        ev_name, scope = None, None
        for f, v in _fields(m):
            if f == 2:
                ev_name = _text(v)
            elif f == 5:
                st = dict(_fields(v))
                if st.get(1) == want[0]:
                    scope = (_text(st[5]) if 5 in st
                             else stat_names.get(st.get(7), ""))
        if ev_name is not None and scope:
            out[ev_name] = scope
    return name, out


def op_scopes(data: bytes) -> dict:
    """device -> {operation name: name stack} from a serialized XSpace."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, scopes = _plane_scopes(plane)
        if name.startswith("/device:TPU:"):
            out[name.split(":")[-1]] = scopes
    return out


def load(directory: str) -> ProgramTrace:
    """The program's spans and op scopes in the trace under ``directory``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {paths}")
    with open(paths[0], "rb") as fh:
        data = fh.read()
    out = ProgramTrace(scopes=op_scopes(data))
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans += [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
                              for e in line.events
                              if e.name.startswith(PREFIX)]
    out.spans.sort(key=lambda s: s.start)
    return out


# -- one clock ----------------------------------------------------------------
def offset_bounds(launched, waited):
    """Bounds (lo, hi) on delta, host time minus device time, or None with
    nothing to bound it by.  ``launched`` holds (module, span) pairs: the
    host launched the module inside the span, so the module starts no
    earlier than the span.  ``waited`` holds pairs in which the host had
    the module's results by the span's end.  lo > hi means the spans
    contradict the device."""
    if not launched or not waited:
        return None
    return (max(s.start - m.start for m, s in launched),
            min(s.end - m.end for m, s in waited))


def _pairs(modules, spans) -> list:
    """The i-th module with the i-th span, both by start; none when the
    counts differ."""
    if len(modules) != len(spans):
        return []
    return list(zip(sorted(modules, key=lambda e: e.start), spans,
                    strict=True))


def clock_pairs(trace: T.Trace, program: ProgramTrace):
    """(launched, waited) pairs to bound delta with.  Serving: each fused
    decode program inside its ``decode_dispatch`` span and waited for in
    its ``decode_wait`` span, and each prefill chunk inside its ``prefill``
    span (a decode program queued behind a chunk starts late, a chunk
    launched onto an idle device does not).  With no such spans, training:
    each step inside the harness span that launched it and waited for in
    the next one, which reads its loss."""
    mods = trace.modules.get(trace.devices[0], []) if trace.devices else []
    if program.named(DISPATCH):
        decode = T.matching(mods, DECODE_PROGRAM)
        return (_pairs(decode, program.named(DISPATCH))
                + _pairs(T.matching(mods, PREFILL_PROGRAM),
                         program.named(PREFILL)),
                _pairs(decode, program.named(WAIT)))
    steps = _pairs(T.matching(mods, TRAIN_PROGRAM),
                   [s for s in trace.spans if s.name == TRAIN_SPAN])
    return steps[:-1], [(m, s) for (m, _), (_, s) in zip(steps, steps[1:])]


def align(trace: T.Trace, program: ProgramTrace) -> float | None:
    """Estimate delta, log its bounds and return the lower one; None (and
    an error in the log) when nothing bounds it or the bounds cross."""
    b = offset_bounds(*clock_pairs(trace, program))
    if b is None:
        log("error: no program pairs up with its spans; host and device "
            "clocks stay unaligned")
        return None
    lo, hi = b
    log(f"host-device clock offset bounds: [{lo / 1e6:.4f}, {hi / 1e6:.4f}] "
        f"ms")
    if lo > hi:
        log("error: the offset interval is empty: the spans contradict the "
            "device")
        return None
    return lo


def idle_gaps(trace: T.Trace, program: ProgramTrace, t0: float, t1: float,
              top: int = 10):
    """``trace.idle_gaps`` with the program's spans beside the harness's,
    all moved onto the device's clock by delta (left as they are when delta
    is not known), so each gap is named by the innermost span, harness or
    program, around its midpoint."""
    d = program.delta
    host = [T.Event(s.name, s.start - (d or 0.0), s.end - (d or 0.0))
            for s in [*trace.spans, *(program.spans if d is not None else [])]]
    return T.idle_gaps(T.Trace(trace.ops, trace.modules, host), t0, t1, top)


# -- what the metric readers share --------------------------------------------
def _trace_dir() -> str | None:
    """The directory the run's trace was loaded from: the ``trace_dir`` of
    ``run.py``'s ``per_layer``, which calls the readers while the
    directory still exists."""
    f = sys._getframe(1)
    while f is not None and f.f_code.co_name != "per_layer":
        f = f.f_back
    d = f.f_locals.get("trace_dir") if f is not None else None
    return d if isinstance(d, str) and os.path.isdir(d) else None


def of(run) -> ProgramTrace | None:
    """The program trace of ``run``, loaded and aligned once; None where
    the run's trace cannot be found."""
    if not hasattr(run, "program"):
        d = _trace_dir()
        run.program = load(d) if d else None
        if run.program is not None:
            run.program.delta = align(run.trace, run.program)
            gaps = idle_gaps(run.trace, run.program, run.t0, run.t1)
            log("longest idle gaps, named on the aligned clock (ms): "
                + ", ".join(f"{n} {1e3 * s:.3f}" for n, s in gaps))
    return run.program


def span_args(run, name: str, keys) -> list[float] | None:
    """Sums of the arguments ``keys`` over the program's spans ``name``
    that start in the traced window; None where there is no such span."""
    pt = of(run)
    spans = T.in_window(pt.named(name), run.t0, run.t1) if pt else []
    if not spans:
        return None
    return [sum(s.args.get(k, 0) for s in spans) for k in keys]


def share(part, whole) -> float | None:
    return 100.0 * part / whole if whole else None
