"""The program's spans and scopes: reading them from a profile, the clock
offset, gap naming on the aligned clock, and the readers that use them."""
import os

import jax
import numpy as np
import pytest

from harness import program_trace as P
from harness import trace as T
from harness.spec import metric_reader

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")
E, S = T.Event, P.Span
ROWS = {"prefill_rows": 16, "prefill_rows_active": 4}
DECODE = {"decode_rows": 16, "decode_rows_live": 10,
          "decode_rows_forced": 4, "decode_rows_emitted": 6}


# -- reading the protobuf -----------------------------------------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num: int, v) -> bytes:
    """One protobuf field: an int as a varint, bytes or str length-delimited,
    a float as fixed64."""
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    if isinstance(v, float):
        return _varint(num << 3 | 1) + np.float64(v).tobytes()
    v = v.encode() if isinstance(v, str) else v
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _plane(name, metas, stats, lines=b""):
    ev = b"".join(_f(4, _f(1, i) + _f(2, m)) for i, m in enumerate(metas))
    st = b"".join(_f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n)))
                  for i, n in stats.items())
    return _f(1, _f(1, 3) + _f(2, name) + _f(3, lines) + ev + st)


def test_op_scopes_by_hand():
    """A direct string, an interned one, other stats and an op with no
    scope; planes that are not a TPU are left out."""
    stats = {7: "tf_op", 8: "jit(f)/mlp/add:", 9: "flops"}
    metas = [_f(2, "%fusion.1 = f32[2]") + _f(5, _f(1, 9) + _f(2, 1.5))
             + _f(5, _f(1, 7) + _f(5, "jit(f)/attention_bwd/dot:")),
             _f(1, 2) + _f(2, "%add.2 = f32[2]") + _f(5, _f(1, 7) + _f(7, 8)),
             _f(2, "%copy.3 = f32[2]") + _f(4, "copy.3")]
    data = (_plane("/device:TPU:0", metas, stats, lines=b"\x00" * 40)
            + _plane("/host:CPU", metas, stats))
    assert P.op_scopes(data) == {"0": {
        "%fusion.1 = f32[2]": "jit(f)/attention_bwd/dot:",
        "%add.2 = f32[2]": "jit(f)/mlp/add:"}}
    assert P.op_scopes(_plane("/device:TPU:1", metas, {9: "flops"})) \
        == {"1": {}}


def test_recorded_op_scopes():
    """Event metadata of a v5e train step's trace (reduced smollm-360m,
    flash kernel forward, XLA reference backward); source locations were
    cut from it.  Scopes come as strings and as interned names."""
    with open(os.path.join(FIXTURE, "v5e_op_metadata.xplane.pb"), "rb") as fh:
        scopes = P.op_scopes(fh.read())["0"]
    assert len(scopes) == 12
    bwd = [k for k, v in scopes.items() if "attention_bwd" in v]
    assert len(bwd) == 6
    assert all("/attention/attention_bwd/" in scopes[k] for k in bwd)
    assert any(k.startswith("%fusion.461 = bf16[2,32,256]") for k in bwd)
    assert scopes[next(k for k in scopes if k.startswith("%fusion.303 "))] \
        == "jit(train_step)/adamw/convert_element_type:"
    assert scopes[next(k for k in scopes if k.startswith("%reduce_sum.252"))] \
        == "jit(train_step)/jvp()/reduce_sum:"


# -- one clock ---------------------------------------------------------------
def test_offset_bounds():
    m1 = E("jit__fused_steps(1)", 100, 200)
    m2 = E("jit__fused_steps(1)", 400, 500)
    launched = [(m1, S("d", 90, 105)), (m2, S("d", 395, 402))]
    waited = [(m1, S("w", 105, 210)), (m2, S("w", 402, 520))]
    assert P.offset_bounds(launched, waited) == (-5, 10)
    # the second wait ends before its program does, by more than the first
    # launch allows: the interval is empty
    assert P.offset_bounds(launched, [waited[0], (m2, S("w", 402, 490))]) \
        == (-5, -10)
    assert P.offset_bounds(launched, []) is None
    assert P.offset_bounds([], waited) is None


def _serve_trace():
    """Host: one harness step [0, 100) with the engine's spans inside.
    Device: a prefill chunk [12, 24) and a fused decode [27, 70).  The
    chunk bounds delta from below at -2, the decode at -7 (it queued behind
    the chunk); the wait bounds it from above at 10.  On the device's clock
    (host time - delta) the spans are admit [4, 12), prefill [12, 22),
    dispatch [22, 32), wait [32, 82), unpack [82, 97)."""
    ops = [E("fusion.1", 12, 24), E("fusion.2", 27, 70)]
    mods = [E("jit__prefill_chunk(2)", 12, 24),
            E("jit__fused_steps(1)", 27, 70)]
    tr = T.Trace({"0": ops}, {"0": mods}, [E("chipbench.engine_step", 0, 100)])
    pt = P.ProgramTrace([
        S("repro.serve.step", 1, 99), S("repro.serve.admit", 2, 10),
        S("repro.serve.prefill", 10, 20, dict(ROWS)),
        S("repro.serve.decode_dispatch", 20, 30),
        S("repro.serve.decode_wait", 30, 80),
        S("repro.serve.unpack", 80, 95, dict(DECODE))])
    return tr, pt


def test_align_takes_the_lower_bound(capsys):
    tr, pt = _serve_trace()
    launched, waited = P.clock_pairs(tr, pt)
    assert [s.name for _, s in launched] == ["repro.serve.decode_dispatch",
                                             "repro.serve.prefill"]
    assert P.offset_bounds(launched[:1], waited) == (-7, 10)
    assert P.offset_bounds(launched, waited) == (-2, 10)
    assert P.align(tr, pt) == -2
    assert "offset bounds: [-0.0000, 0.0000] ms" in capsys.readouterr().err
    late = P.ProgramTrace([*pt.spans[:4], S("repro.serve.decode_wait", 30, 60),
                           pt.spans[5]])
    assert P.align(tr, late) is None
    assert "error: the offset interval is empty" in capsys.readouterr().err


def test_train_steps_bound_the_offset():
    """Train step k starts inside the harness span that launched it and
    ends inside the next one, which waits for its loss."""
    tr = T.Trace({"0": []}, {"0": [E("jit_train_step(3)", 0, 50),
                                   E("jit_train_step(3)", 50, 100),
                                   E("jit_train_step(3)", 100, 150)]},
                 [E("chipbench.train_step", 0, 48),
                  E("chipbench.train_step", 49, 99),
                  E("chipbench.train_step", 99, 120)])
    assert P.offset_bounds(*P.clock_pairs(tr, P.ProgramTrace())) == (0, 20)


def test_gaps_are_named_by_the_innermost_span_on_the_aligned_clock():
    tr, pt = _serve_trace()
    pt.delta = P.align(tr, pt)
    # device gaps [0, 12), [24, 27), [70, 100)
    assert P.idle_gaps(tr, pt, 0, 100) == [
        ["repro.serve.unpack", pytest.approx(30e-9)],
        ["repro.serve.admit", pytest.approx(12e-9)],
        ["repro.serve.decode_dispatch", pytest.approx(3e-9)]]
    # without program spans the harness's answer is today's
    bare = P.ProgramTrace()
    assert P.idle_gaps(tr, bare, 0, 100) == T.idle_gaps(tr, 0, 100)


# -- the readers -------------------------------------------------------------
class _Run:
    pass


def _run(tr, pt, span="chipbench.engine_step"):
    r = _Run()
    r.trace, (r.t0, r.t1) = tr, tr.window(span)
    if pt is not None:
        pt.delta = P.align(tr, pt)
    r.program = pt
    return r


def test_serving_readers_by_hand():
    r = _run(*_serve_trace())
    assert metric_reader("prefill_row_use")(r) == pytest.approx(25.0)
    assert metric_reader("decode_row_use")(r) == pytest.approx(62.5)
    assert metric_reader("forced_decode_share")(r) == pytest.approx(40.0)
    # idle on the device's clock: admit 8, prefill 0, dispatch 3, unpack 15
    assert metric_reader("engine_host_idle_ms_per_sync")(r) == \
        pytest.approx(26e-6)


def test_attention_backward_per_step_by_hand():
    """Two steps finish in the window, a third does not; a loop's event
    holds two of the scoped ops and is not itself counted."""
    bwd = "jit(train_step)/transpose(jvp())/attention/attention_bwd/dot:"
    ops = [E("while.1", 0, 40), E("fusion.1", 5, 15), E("fusion.2", 20, 30),
           E("fusion.3", 55, 70), E("fusion.1", 105, 115)]
    tr = T.Trace({"0": ops}, {"0": [E("jit_train_step(3)", 0, 50),
                                    E("jit_train_step(3)", 50, 100),
                                    E("jit_train_step(3)", 100, 150)]},
                 [E("chipbench.train_step", 0, 48),
                  E("chipbench.train_step", 49, 99)])
    pt = P.ProgramTrace(scopes={"0": {
        "while.1": "jit(train_step)/while", "fusion.1": bwd,
        "fusion.2": "jit(train_step)/mlp/dot:", "fusion.3": bwd}})
    r = _run(tr, pt, "chipbench.train_step")
    assert (r.t0, r.t1) == (0, 115)
    assert metric_reader("attention_bwd_ms_per_step")(r) == \
        pytest.approx((10 + 15) / 2 / 1e6)


@pytest.mark.parametrize("program", [None, P.ProgramTrace()])
def test_readers_find_nothing_without_program_spans(program):
    """A program without spans or scopes, as before they existed, or a run
    whose trace cannot be found: every reader returns nothing."""
    tr, _ = _serve_trace()
    r = _run(tr, program)
    for name in ("prefill_row_use", "decode_row_use", "forced_decode_share",
                 "engine_host_idle_ms_per_sync", "attention_bwd_ms_per_step"):
        assert metric_reader(name)(r) is None, name


# -- the engine under the profiler --------------------------------------------
SPANS = ("repro.serve.step", "repro.serve.admit", "repro.serve.prefill",
         "repro.serve.decode_dispatch", "repro.serve.decode_wait",
         "repro.serve.unpack")


def _serve(trace_dir=None):
    """Four requests on a tiny engine; the profiler, if any, records every
    step after the first.  Returns outputs, stats before and after the
    traced steps."""
    from repro.configs import reduced_config
    from repro.models import lm
    from repro.serve.engine import DecodeEngine, Request

    cfg = reduced_config("smollm-360m")
    eng = DecodeEngine(cfg, lm.init_lm(cfg, jax.random.PRNGKey(0)),
                       batch_slots=4, max_seq=64, prefill_chunk=4,
                       steps_per_sync=4)
    reqs = [Request(prompt=np.arange(L, dtype=np.int32) + L, max_new_tokens=n)
            for L, n in ((3, 2), (7, 3), (10, 5), (13, 4), (6, 6))]
    for r in reqs:
        eng.submit(r)
    eng.step()
    before = dict(eng.stats)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    eng.run_until_drained()
    if trace_dir:
        jax.profiler.stop_trace()
    return [[int(t) for t in r.output] for r in reqs], before, dict(eng.stats)


def test_engine_spans_and_their_counts_under_the_profiler(tmp_path):
    plain, _, _ = _serve()
    traced, before, after = _serve(str(tmp_path))
    assert traced == plain
    pt = P.load(str(tmp_path))
    assert {s.name for s in pt.spans} == set(SPANS)
    steps = pt.named("repro.serve.step")
    for s in pt.spans:
        assert any(p.start <= s.start and s.end <= p.end for p in steps), s
    for name, keys in (("repro.serve.prefill", ROWS),
                       ("repro.serve.unpack", DECODE)):
        for k in keys:
            assert sum(s.args[k] for s in pt.named(name)) \
                == after[k] - before[k] > 0, k
    # a reader called from the harness's ``per_layer`` loads the same trace
    r = _Run()
    r.trace = T.Trace({"0": []}, {"0": []}, [])
    r.t0, r.t1 = pt.spans[0].start, pt.spans[-1].end

    def per_layer(cell, run, trace_dir, device):
        return metric_reader("decode_row_use")(run)

    d = {k: after[k] - before[k] for k in DECODE}
    assert per_layer(None, r, str(tmp_path), None) == pytest.approx(
        100 * d["decode_rows_live"] / d["decode_rows"])
    assert [s.name for s in r.program.spans] == [s.name for s in pt.spans]
