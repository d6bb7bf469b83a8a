"""Device time of the attention backward (the innermost operations whose
name stack holds the ``attention_bwd`` scope: the XLA reference gradient
of the flash kernel) per train step the device finished in the traced
window, in ms."""
from harness.program_trace import of
from harness.trace import leaves, matching

SCOPE = "attention_bwd"


def read(run):
    pt = of(run)
    dev = run.trace.devices[0]
    steps = [e for e in matching(run.trace.modules[dev], "train_step")
             if run.t0 <= e.end <= run.t1]
    scopes = pt.scopes.get(dev, {}) if pt else {}
    if not steps or not scopes:
        return None
    ns = sum(e.dur for e in leaves(run.trace.ops[dev])
             if SCOPE in scopes.get(e.name, "")
             and any(s.start <= e.start < s.end for s in steps))
    return ns / len(steps) / 1e6 if ns else None
