"""Share (%) of its roofline that the decode attention kernel reached: the
bytes and operations the live slots need at their real ``kv_len`` (all
layers, every decode step of the traced syncs), each chip's share of them,
over the kernel's device time summed over the chips.  The bound is memory:
the kernel streams each live slot's K and V rows once."""
from harness.arith import roofline_share
from harness.trace import in_window, mosaic_calls


def read(run):
    m, n = run.model, run.chips
    # the kernel's signature on one chip: (kv_len, q, k, v) -> one
    # [heads, head_dim] row per slot, that chip's share of them
    ev = [e for d in run.trace.devices for e in mosaic_calls(
        in_window(run.trace.ops[d], run.t0, run.t1),
        run.slots * m.heads * m.head_dim // n, 4)]
    if not ev or run.peaks is None:
        return None
    flops = nbytes = 0
    for rec in run.records:
        for start, k in rec.decode:
            for j in range(k):
                f, b = m.decode_attention_work(start + j + 1)
                flops += f
                nbytes += b
    if not flops:
        return None
    share, _ = roofline_share(m.layers * flops, m.layers * nbytes,
                              sum(e.dur for e in ev) / 1e9, run.peaks)
    return share
