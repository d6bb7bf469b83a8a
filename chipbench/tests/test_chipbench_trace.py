"""The trace reduction gives known busy, idle and kernel times."""
import json
import os

import pytest

from harness import trace as T
from harness.serve import StepRecord
from harness.spec import metric_reader
from reference.dense_gqa import Model, Spec

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")
# a decode attention call as the profile names it: 1 slot, 2 heads of 4
KERNEL = ('%closed_call.9 = bf16[1,1,2,4]{3,2,1,0} custom-call(s32[1]{0} %l, '
          'bf16[1,1,2,4]{3,2,1,0} %q, bf16[1,1,64,4]{3,2,1,0} %k, '
          'bf16[1,1,64,4]{3,2,1,0} %v), custom_call_target="tpu_custom_call"')


def _hand_trace():
    """Device 0: ops [0,10) [5,15) [20,30) [40,50) ns, two modules; the host
    spans one step over [0, 32) and one over [35, 60)."""
    E = T.Event
    ops = [E("fusion.1", 0, 10), E(KERNEL, 5, 15),
           E("fusion.1", 20, 30), E(KERNEL, 40, 50)]
    mods = [E("jit__fused_steps(1)", 0, 30), E("jit__prefill_chunk(2)", 40, 50)]
    spans = [E("chipbench.engine_step", 0, 32),
             E("chipbench.engine_step", 35, 60)]
    return T.Trace({"0": ops}, {"0": mods}, spans)


def test_union_busy_and_gaps_by_hand():
    tr = _hand_trace()
    assert T.union(tr.ops["0"], 0, 60) == [(0, 15), (20, 30), (40, 50)]
    assert T.busy_ns(tr, 0, 60) == 35
    assert T.busy_ns(tr, 8, 22) == 9
    assert tr.window("chipbench.engine_step") == (0, 60)
    gaps = T.idle_gaps(tr, 0, 60)
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 10e-9, 5e-9])
    assert {n for n, _ in gaps} == {"chipbench.engine_step"}
    assert T.idle_gaps(tr, 30, 34) == [["no span", pytest.approx(4e-9)]]
    tops = dict((k, v) for k, v in T.op_totals(tr, 0, 60))
    assert tops == pytest.approx({"fusion.1": 20e-9, KERNEL: 20e-9})


def test_nested_operations_count_once():
    E = T.Event
    tr = T.Trace({"0": [E("while", 0, 100), E("a", 10, 20), E("b", 30, 90),
                        E("b", 40, 50)]}, {"0": []}, [])
    assert [e.name for e in T.leaves(tr.ops["0"])] == ["a", "b"]
    assert T.busy_ns(tr, 0, 100) == 100
    assert dict(T.op_totals(tr, 0, 100)) == pytest.approx(
        {"a": 10e-9, "b": 10e-9})


class _Run:
    pass


def _run(tr):
    r = _Run()
    r.trace, (r.t0, r.t1) = tr, tr.window("chipbench.engine_step")
    r.peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    r.steps_per_sync = 2
    r.slots = 1
    r.model = Model(Spec(layers=1, d_model=8, heads=2, kv_heads=1,
                         head_dim=4, d_ff=16, vocab=10, rope_theta=1e4,
                         eps=1e-6, qk_norm=False))
    r.chips = 1
    r.records = [StepRecord(0, 1, decode=[(3, 2)]), StepRecord(1, 2)]
    for rec, s in zip(r.records, tr.spans):
        rec.span = s
    return r


def test_serving_metrics_by_hand():
    r = _run(_hand_trace())
    # idle inside the spans: (32 - 25) + (25 - 10) ns over 1 sync
    assert metric_reader("engine_idle_ms_per_sync")(r) == pytest.approx(22e-6)
    assert metric_reader("decode_step_ms")(r) == pytest.approx(15e-6)
    assert metric_reader("prefill_chunk_ms")(r) == pytest.approx(10e-6)
    assert metric_reader("idle_share.serve")(r) == pytest.approx(
        100 * 25 / 60)
    # decode attention: kv_len 4 and 5; bytes 2*(4+5)*1*4*2 + 2*(2*2*4*2)
    nbytes = 2 * 9 * 4 * 2 + 2 * 2 * 4 * 2 * 2
    assert metric_reader("decode_attention_roofline")(r) == pytest.approx(
        100 * (nbytes / 1e9) / 20e-9)


def test_metrics_that_find_nothing_return_nothing():
    r = _run(T.Trace({"0": []}, {"0": []}, _hand_trace().spans))
    for name in ("decode_step_ms", "prefill_chunk_ms",
                 "decode_attention_roofline", "mfu.decode"):
        assert metric_reader(name)(r) is None


def test_recorded_chip_trace():
    """Three spans on a v5e, each running the decode kernel (2 slots,
    8 heads of 128, 512 cached rows), the flash kernel (256 tokens, 8 heads
    of 64) and a matmul.  The expectations were computed when the trace
    was recorded, by a sweep over the events' ends in the spans' window."""
    with open(os.path.join(FIXTURE, "v5e_trace.json")) as fh:
        rec = json.load(fh)
    tr = T.Trace.from_json(rec["trace"])
    spans = [e for e in tr.spans if e.name == rec["span"]]
    s0, s1 = spans[0].start, spans[-1].end
    assert T.busy_ns(tr, s0, s1) == pytest.approx(rec["expected"]["busy_ns"])
    kern = T.matching(T.in_window(tr.ops["0"], s0, s1), rec["kernel"])
    assert len(kern) == rec["expected"]["kernel_calls"]
    assert sum(e.dur for e in kern) == pytest.approx(
        rec["expected"]["kernel_ns"])
    # the whole trace: three calls of each kernel, known by signature
    t0, t1 = tr.window(rec["span"])
    ops = T.in_window(tr.ops["0"], t0, t1)
    decode = T.mosaic_calls(ops, 2 * 8 * 128, 4)
    flash = T.mosaic_calls(ops, 256 * 8 * 64, 3)
    assert len(decode) == 3 and len(flash) == 3
    assert not set(map(id, decode)) & set(map(id, flash))
    assert T.busy_ns(tr, t0, t1) <= sum(e.dur for e in T.leaves(ops)) + 1
