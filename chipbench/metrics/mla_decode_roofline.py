"""Share (%) of its roofline that decode's latent attention reached: the
operations and bytes the live slots need at their real ``kv_len``
(``mla_decode_work``: scores and values over each live latent row, the row
read once; all layers, every decode step of the traced syncs), over the
device time of the operations under the ``mla_latent_attention`` scope in
the fused decode programs.  The bound is memory."""
from harness.arith import roofline_share
from harness.readers import per_step_ms, program_calls
from harness.scopes import scope_test


def read(run):
    m = run.model
    test = scope_test(run, "mla_latent_attention")
    ms = test and per_step_ms(run, test, "_fused_steps")
    if not ms or run.peaks is None or not hasattr(m, "mla_decode_work"):
        return None
    flops = nbytes = 0
    for rec in run.records:
        for start, k in rec.decode:
            for j in range(k):
                f, b = m.mla_decode_work(start + j + 1)
                flops += f
                nbytes += b
    if not flops:
        return None
    seconds = ms * len(program_calls(run, "_fused_steps")) / 1e3
    share, _ = roofline_share(m.all_layers * flops, m.all_layers * nbytes,
                              seconds, run.peaks)
    return share
