"""Model FLOPs of the live slots at their real context (every decode step
of the traced syncs) over the fused decode program's device time, as a
share (%) of the peak of the chips the program runs on."""
from harness.readers import program_calls


def read(run):
    calls = program_calls(run, "_fused_steps")
    if not calls or run.peaks is None:
        return None
    flops = sum(run.model.decode_token_flops(start + j + 1)
                for rec in run.records for start, n in rec.decode
                for j in range(n))
    seconds = sum(e.dur for e in calls) / 1e9
    return 100.0 * flops / (seconds * run.chips * run.peaks["bf16_flops"])
