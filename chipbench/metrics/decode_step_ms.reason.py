"""Device time of the fused decode program (``_fused_steps``) per decode
step, in ms."""
from harness.readers import ms_per_call


def read(run):
    return ms_per_call(run, "_fused_steps", run.steps_per_sync)
