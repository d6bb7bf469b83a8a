"""Share (%) of its roofline that the flash attention forward kernel
reached: causal forward operations and the q, k, v, o bytes of one layer's
call at the cell's batch and sequence length, times the calls in the
traced window, over the kernel's device time."""
from harness.arith import flash_attention_work, roofline_share
from harness.trace import in_window, mosaic_calls


def read(run):
    a, dev = run.arch, run.trace.devices[0]
    # the kernel's signature: (q, k, v) -> o, as many values as q; the
    # recomputation in the backward pass is the same forward call
    ev = mosaic_calls(in_window(run.trace.ops[dev], run.t0, run.t1),
                      run.batch * run.seq * a.heads * a.head_dim, 3)
    if not ev or run.peaks is None:
        return None
    flops, nbytes = flash_attention_work(a, run.batch, run.seq)
    share, _ = roofline_share(len(ev) * flops, len(ev) * nbytes,
                              sum(e.dur for e in ev) / 1e9, run.peaks)
    return share
