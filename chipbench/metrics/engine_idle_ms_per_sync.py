"""Device idle time inside the harness's span around each
``DecodeEngine.step`` call, per fused sync, in ms."""
from harness.trace import busy_ns


def read(run):
    spans = [r.span for r in run.records if hasattr(r, "span")]
    syncs = sum(1 for r in run.records if r.decode)
    if not spans or not syncs:
        return None
    idle = sum(s.dur - busy_ns(run.trace, s.start, s.end) for s in spans)
    return idle / syncs / 1e6
