"""Forward and backward model FLOPs (no recomputation) of the train steps
the device finished in the traced window, over the window, as a share (%)
of the peak of all the chips the step runs on.  The steps are counted on
each chip and averaged."""
from harness.trace import matching

PROGRAM = "train_step"


def read(run):
    devs = run.trace.devices
    steps = sum(len([e for e in matching(run.trace.modules[d], PROGRAM)
                     if run.t0 <= e.end <= run.t1]) for d in devs) / len(devs)
    if not steps or run.peaks is None:
        return None
    flops = steps * run.model.train_step_flops(run.batch, run.seq)
    seconds = (run.t1 - run.t0) / 1e9
    return 100.0 * flops / (seconds * run.chips * run.peaks["bf16_flops"])
