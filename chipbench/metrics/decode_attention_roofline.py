"""Share (%) of its roofline that the decode attention kernel reached: the
bytes and operations the live slots need at their real ``kv_len`` (all
layers, every decode step of the traced syncs) over the kernel's device
time.  The bound is memory: the kernel streams each live slot's K and V
rows once."""
from harness.arith import decode_attention_work, roofline_share
from harness.trace import in_window, mosaic_calls


def read(run):
    a, dev = run.arch, run.trace.devices[0]
    # the kernel's signature: (kv_len, q, k, v) -> one [heads, head_dim]
    # row per slot
    ev = mosaic_calls(in_window(run.trace.ops[dev], run.t0, run.t1),
                      run.slots * a.heads * a.head_dim, 4)
    if not ev or run.peaks is None:
        return None
    flops = nbytes = 0
    for rec in run.records:
        for start, n in rec.decode:
            for j in range(n):
                f, b = decode_attention_work(a, start + j + 1)
                flops += f
                nbytes += b
    if not flops:
        return None
    share, _ = roofline_share(a.layers * flops,
                              a.layers * nbytes,
                              sum(e.dur for e in ev) / 1e9, run.peaks)
    return share
