"""Device idle time inside the engine's own host spans ``repro.serve.admit``,
``prefill``, ``decode_dispatch`` and ``unpack`` (not ``decode_wait``, in
which the host waits on the device), on the device's clock, per fused sync
in the traced window, in ms.  None where the clocks cannot be aligned."""
from harness.program_trace import of
from harness.trace import busy_ns, in_window

HOST = ("repro.serve.admit", "repro.serve.prefill",
        "repro.serve.decode_dispatch", "repro.serve.unpack")


def read(run):
    pt = of(run)
    if pt is None or pt.delta is None:
        return None
    spans = in_window(pt.spans, run.t0, run.t1)
    syncs = sum(s.name == "repro.serve.decode_dispatch" for s in spans)
    if not syncs:
        return None
    d = pt.delta
    idle = sum(s.dur - busy_ns(run.trace, s.start - d, s.end - d)
               for s in spans if s.name in HOST)
    return idle / syncs / 1e6
