"""The training readers over several chips, on a synthetic trace of four
devices: each reads every chip, and with one device each reads what it
read before."""
import pytest

from harness import program_trace as P
from harness import trace as T
from harness.spec import metric_reader
from reference.dense_gqa import Model, Spec

E = T.Event
MODEL = Model(Spec(layers=1, d_model=8, heads=2, kv_heads=1, head_dim=4,
                   d_ff=16, vocab=10, rope_theta=1e4, eps=1e-6,
                   qk_norm=False))
BATCH, SEQ = 2, 8
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def _flash(elements):
    """A flash forward call as the profile names it: (q, k, v) -> o."""
    shape = f"bf16[1,{elements // 4},1,4]{{3,2,1,0}}"
    return (f"%closed_call.3 = {shape} custom-call({shape} %q, {shape} %k, "
            f"{shape} %v), custom_call_target=\"tpu_custom_call\"")


BWD = "jit(train_step)/transpose(jvp())/attention/attention_bwd/dot:"


def _device(d: int, chips: int) -> list:
    """Device ``d``: two flash calls, a backward op, three collectives and
    a matmul, the all-gather and the matmul d ns longer on device d: busy
    over [0, 25 + d), [50, 66) and [70, 80 + d)."""
    flash = _flash(BATCH * SEQ * MODEL.heads * MODEL.head_dim // chips)
    return [E(flash, 0, 10), E("fusion.1", 10, 20),
            E(f"%all-gather.1 = bf16[8]{{0}} all-gather(bf16[2]{{0}} %p)",
              20, 25 + d),
            E(flash, 50, 60),
            E("%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} "
              "%g)", 60, 62),
            E("%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} "
              "%s)", 62, 66),
            E("fusion.2", 70, 80 + d)]


def _run(chips: int):
    devs = [str(d) for d in range(chips)]
    steps = [E("jit_train_step(3)", 0, 50), E("jit_train_step(3)", 50, 100),
             E("jit_train_step(3)", 100, 150)]
    tr = T.Trace({d: _device(int(d), chips) for d in devs},
                 {d: list(steps) for d in devs},
                 [E("chipbench.train_step", 0, 48),
                  E("chipbench.train_step", 49, 100)])

    class Run:
        pass

    r = Run()
    r.trace, (r.t0, r.t1) = tr, tr.window("chipbench.train_step")
    r.peaks, r.model, r.chips, r.batch, r.seq = PEAKS, MODEL, chips, \
        BATCH, SEQ
    r.program = P.ProgramTrace(scopes={d: {"fusion.1": BWD} for d in devs})
    return r


def test_mfu_train_counts_every_chip_and_stays_under_the_peak():
    one, four = _run(1), _run(4)
    assert (one.t0, one.t1) == (0, 100)
    flops = 2 * MODEL.train_step_flops(BATCH, SEQ)     # 2 steps end by 100
    want = 100 * flops / (100e-9 * PEAKS["bf16_flops"])
    assert metric_reader("mfu.train")(one) == pytest.approx(want)
    assert metric_reader("mfu.train")(four) == pytest.approx(want / 4)
    assert 0 < metric_reader("mfu.train")(four) <= 100


def test_idle_share_train_averages_the_chips():
    # device d is busy 51 + 2 d ns of the window [0, 100)
    busy = [51 + 2 * d for d in range(4)]
    assert metric_reader("idle_share.train")(_run(4)) == pytest.approx(
        100 * (1 - sum(busy) / 4 / 100))
    assert metric_reader("idle_share.train")(_run(1)) == pytest.approx(49.0)


@pytest.mark.parametrize("chips", [1, 4])
def test_flash_roofline_takes_each_chips_share(chips):
    """Each chip runs two calls of its share of one layer's work, 10 ns
    each: twice the whole layer's work over 20 ns on each chip."""
    flops, nbytes = MODEL.flash_attention_work(BATCH, SEQ)
    want = 100 * 2 * max(flops / PEAKS["bf16_flops"],
                         nbytes / PEAKS["hbm_bytes_per_s"]) / (chips * 20e-9)
    assert metric_reader("flash_attention_roofline")(_run(chips)) == \
        pytest.approx(want)


@pytest.mark.parametrize("chips", [1, 4])
def test_attention_backward_averages_the_chips(chips):
    assert metric_reader("attention_bwd_ms_per_step")(_run(chips)) == \
        pytest.approx(10 / 2 / 1e6)


def test_collective_time_per_step_averages_the_chips():
    # device d: all-gather 5 + d ns, all-reduce start 2 and done 4 ns, over
    # the 2 steps that end in the window
    want = sum((5 + d + 2 + 4) / 2 for d in range(4)) / 4 / 1e6
    assert metric_reader("collective_ms_per_step")(_run(4)) == \
        pytest.approx(want)


def test_collective_time_reads_nothing_without_collectives():
    r = _run(1)
    for d, ops in r.trace.ops.items():
        r.trace.ops[d] = [e for e in ops if "all-" not in e.name]
    assert metric_reader("collective_ms_per_step")(r) is None
