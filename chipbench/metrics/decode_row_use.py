"""Share (%) of the rows the fused decode program computed that belong to
a live slot: the ``decode_rows_live`` over the ``decode_rows`` arguments of
the engine's ``repro.serve.unpack`` spans in the traced window.  A sync
computes all ``slots x steps_per_sync`` rows."""
from harness.program_trace import share, span_args


def read(run):
    v = span_args(run, "repro.serve.unpack",
                  ("decode_rows_live", "decode_rows"))
    return share(*v) if v else None
