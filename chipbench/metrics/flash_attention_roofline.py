"""Share (%) of its roofline that the flash attention forward kernel
reached: causal forward operations and the q, k, v, o bytes of one layer's
call at the cell's batch and sequence length, each chip's share of them
(the batch split over the data axis, the heads over the model axis) times
the calls on each chip in the traced window, over the kernel's device time
summed over the chips."""
from harness.arith import roofline_share
from harness.trace import in_window, mosaic_calls


def read(run):
    m, n = run.model, run.chips
    # the kernel's signature on one chip: (q, k, v) -> o, as many values as
    # that chip's q; the recomputation in the backward pass is the same call
    per_chip = run.batch * run.seq * m.heads * m.head_dim // n
    ev = [e for d in run.trace.devices for e in mosaic_calls(
        in_window(run.trace.ops[d], run.t0, run.t1), per_chip, 3)]
    if not ev or run.peaks is None:
        return None
    flops, nbytes = m.flash_attention_work(run.batch, run.seq)
    share, _ = roofline_share(len(ev) * flops / n, len(ev) * nbytes / n,
                              sum(e.dur for e in ev) / 1e9, run.peaks)
    return share
