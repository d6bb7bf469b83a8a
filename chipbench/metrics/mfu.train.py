"""Forward and backward model FLOPs (no recomputation) of the train steps
the device finished in the traced window, over the window, as a share (%)
of the chip's peak."""
from harness.arith import train_step_flops
from harness.trace import matching

PROGRAM = "train_step"


def read(run):
    dev = run.trace.devices[0]
    steps = [e for e in matching(run.trace.modules[dev], PROGRAM)
             if run.t0 <= e.end <= run.t1]
    if not steps or run.peaks is None:
        return None
    flops = len(steps) * train_step_flops(run.arch, run.batch, run.seq)
    seconds = (run.t1 - run.t0) / 1e9
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
