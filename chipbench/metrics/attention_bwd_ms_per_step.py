"""Device time of the attention backward (the innermost operations whose
name stack holds the ``attention_bwd`` scope: the XLA reference gradient
of the flash kernel) per train step the device finished in the traced
window, in ms, averaged over the chips."""
from harness.program_trace import of
from harness.readers import per_step_ms

SCOPE = "attention_bwd"


def read(run):
    pt = of(run)
    if pt is None:
        return None
    return per_step_ms(run, lambda d, e: SCOPE in pt.scopes.get(d, {}).get(
        e.name, ""))
