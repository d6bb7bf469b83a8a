"""Roofline extraction: HLO collective parsing, term math, extrapolation."""
import pytest

from repro.launch.roofline import PEAKS, V5E, analyze, parse_collectives

HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[4,1024]{1,0} parameter(0)
  %ag = bf16[8,1024]{1,0} all-gather(%p0), replica_groups={}
  %ar = f32[256]{0} all-reduce(%x), to_apply=%add
  %rs = f32[2,64]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = bf16[16,32]{1,0} all-to-all(%z), dimensions={0}
  %cp = u8[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %ag2s = (bf16[4,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(%q)
  %ag2d = bf16[8,8]{1,0} all-gather-done(%ag2s)
}
"""


def test_parse_collectives_kinds_and_bytes():
    st = parse_collectives(HLO)
    assert st.count_by_kind["all-gather"] >= 1
    assert st.count_by_kind["all-reduce"] == 1
    # all-gather result: 8*1024*2 bytes
    assert st.bytes_by_kind["all-gather"] >= 8 * 1024 * 2
    # all-reduce: 2x factor on 256*4 bytes
    assert st.bytes_by_kind["all-reduce"] == 2 * 256 * 4
    assert st.bytes_by_kind["reduce-scatter"] == 2 * 64 * 4
    assert st.bytes_by_kind["all-to-all"] == 16 * 32 * 2
    assert st.bytes_by_kind["collective-permute"] == 128


def test_analyze_terms_and_dominant():
    r = analyze(arch="x", shape="train_4k", mesh_desc="data16xmodel16",
                chips=256,
                cost={"flops": 1e12, "bytes accessed": 1e9},
                hlo_text=HLO, model_flops=200e12)
    chip = PEAKS[V5E]
    assert r.compute_s == pytest.approx(1e12 * 256 / (256 * chip["flops"]))
    assert r.memory_s == pytest.approx(1e9 * 256 / (256 * chip["hbm_bw"]))
    assert r.collective_s == pytest.approx(
        r.collective_bytes_per_chip / chip["ici_bw"])
    assert r.dominant == "compute"
    assert 0 < r.useful_ratio <= 1.0
    assert 0 < r.roofline_fraction <= 1.0


def test_probe_extrapolation_linear():
    """m(L) = a + b*L measured at two L values extrapolates exactly."""
    from repro.launch.aggregate import extrapolate_linear

    base = {"hlo_flops": 10.0, "hlo_bytes": 4.0,
            "collective_bytes_per_chip": 2.0}
    bumped = [{"hlo_flops": 16.0, "hlo_bytes": 5.0,
               "collective_bytes_per_chip": 3.5}]
    full = extrapolate_linear(base, bumped, base_counts=(2,),
                              full_counts=(32,))
    assert full["hlo_flops"] == pytest.approx(10 + 6 * 30)
    assert full["hlo_bytes"] == pytest.approx(4 + 1 * 30)
    assert full["collective_bytes_per_chip"] == pytest.approx(2 + 1.5 * 30)


def test_probe_extrapolation_two_segments():
    from repro.launch.aggregate import extrapolate_linear

    base = {"hlo_flops": 10.0}
    bumped = [{"hlo_flops": 13.0}, {"hlo_flops": 15.0}]  # +seg0, +seg1
    full = extrapolate_linear(base, bumped, base_counts=(1, 2),
                              full_counts=(3, 58))
    assert full["hlo_flops"] == pytest.approx(10 + 3 * 2 + 5 * 56)
