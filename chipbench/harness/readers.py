"""What the per-layer metric files share: device time of one program per
call, and of chosen operations per finished step, in the traced window."""
from __future__ import annotations

import bisect

from harness.trace import in_window, leaves, matching


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


def per_step_ms(run, counts, program: str = "train_step"):
    """Device ms a finished step of ``program`` spends in the innermost
    operations ``counts(device, event)`` accepts, averaged over the chips;
    None where the window finished no step or no operation counted."""
    per_chip = []
    for d in run.trace.devices:
        steps = sorted((e for e in matching(run.trace.modules[d], program)
                        if run.t0 <= e.end <= run.t1), key=lambda e: e.start)
        if not steps:
            return None
        starts = [s.start for s in steps]

        def inside(t, steps=steps, starts=starts):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < steps[i].end

        ns = sum(e.dur for e in leaves(run.trace.ops[d])
                 if inside(e.start) and counts(d, e))
        per_chip.append(ns / len(steps))
    total = sum(per_chip)
    return total / len(per_chip) / 1e6 if total else None
