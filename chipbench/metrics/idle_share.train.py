"""Share (%) of the traced training window in which no operation ran on
the device: 1 - the union of busy intervals over the window, averaged over
the chips."""
from harness.trace import busy_ns


def read(run):
    return 100.0 * (1.0 - busy_ns(run.trace, run.t0, run.t1)
                    / (run.t1 - run.t0))
