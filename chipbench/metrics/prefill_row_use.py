"""Share (%) of the rows the chunked-prefill program computed that belong
to a slot being prefilled: the ``prefill_rows_active`` over the
``prefill_rows`` arguments of the engine's ``repro.serve.prefill`` spans in
the traced window.  A chunk call computes all ``slots x chunk`` rows."""
from harness.program_trace import share, span_args


def read(run):
    v = span_args(run, "repro.serve.prefill",
                  ("prefill_rows_active", "prefill_rows"))
    return share(*v) if v else None
