"""Device time of the chunked-prefill program (``_prefill_chunk``) per
call, in ms."""
from harness.readers import ms_per_call


def read(run):
    return ms_per_call(run, "_prefill_chunk")
