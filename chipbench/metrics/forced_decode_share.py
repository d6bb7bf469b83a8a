"""Share (%) of the live decode rows that fed a prompt token (forced
decode of the prompt's tail after its last whole prefill chunk) instead of
sampling one: ``decode_rows_forced`` over ``decode_rows_live`` on the
engine's ``repro.serve.unpack`` spans in the traced window."""
from harness.program_trace import share, span_args


def read(run):
    v = span_args(run, "repro.serve.unpack",
                  ("decode_rows_forced", "decode_rows_live"))
    return share(*v) if v else None
