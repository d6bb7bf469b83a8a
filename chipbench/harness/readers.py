"""What the per-layer metric files share: device time of one program,
per call, in the traced window."""
from __future__ import annotations

from harness.trace import in_window, matching


def program_calls(run, program: str):
    """Events of the jitted program ``program`` (its module name) that
    start in the traced window on the first device."""
    dev = run.trace.devices[0]
    return matching(in_window(run.trace.modules[dev], run.t0, run.t1),
                    program)


def ms_per_call(run, program: str, per_call: int = 1):
    """Device ms of ``program`` per call, divided by ``per_call`` units of
    work each call does; None where the window ran it not once."""
    calls = program_calls(run, program)
    if not calls:
        return None
    return sum(e.dur for e in calls) / (len(calls) * per_call) / 1e6
