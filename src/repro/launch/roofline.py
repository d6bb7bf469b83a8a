"""Roofline-term extraction from a compiled (dry-run) artifact.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.  Collective
bytes are NOT in cost_analysis: we parse the partitioned HLO
(``compiled.as_text()``) and sum the buffer sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, weighted by
the ring-algorithm traffic factor (all-reduce moves ~2x its payload).

Hardware model: the v5e row of ``PEAKS`` — 197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field, asdict

import numpy as np

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip).  ici_bw is this model's conservative
# single-link share of that interconnect, not a published figure.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
V5E = "TPU v5 lite"      # the dry-run model's target chip


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``.  A kind missing
    from ``PEAKS`` is an error, never a default: a share computed against
    another chip's peaks would be wrong."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS "
                       f"(known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

# result shape of a collective op line, e.g.:
#   %ag = bf16[2,4096,512]{...} all-gather(...)
_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

# traffic factor per op kind (ring algorithms, bytes on the wire per chip
# relative to the printed buffer size)
_FACTOR = {
    "all-gather": 1.0,        # result is the gathered buffer
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    if not dims:
        return b
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum collective buffer bytes (per partition; post-SPMD HLO shapes are
    per-device) weighted by ring traffic factors.

    `-start/-done` pairs are de-duplicated by only counting `-start` when
    both forms appear for async collectives (the regex tags both; `-done`
    results repeat the buffer)."""
    stats = CollectiveStats()
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        # skip the -done halves of async pairs
        tail = hlo_text[m.end(3):m.end(3) + 6]
        if hlo_text[m.start():m.end()].endswith("-done("):
            continue
        nbytes = _shape_bytes(dtype, dims) * _FACTOR[kind]
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float             # whole-step, all chips
    hlo_bytes: float
    collective_bytes_per_chip: float
    collectives: dict
    collective_counts: dict
    model_flops: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    useful_ratio: float = 0.0
    roofline_fraction: float = 0.0
    bytes_per_device: float = 0.0
    note: str = ""

    def finalize(self):
        chip = PEAKS[V5E]
        self.compute_s = self.hlo_flops / (self.chips * chip["flops"])
        self.memory_s = self.hlo_bytes / (self.chips * chip["hbm_bw"])
        self.collective_s = self.collective_bytes_per_chip / chip["ici_bw"]
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.dominant = max(terms, key=terms.get)
        self.useful_ratio = (self.model_flops / self.hlo_flops
                             if self.hlo_flops else 0.0)
        bound = max(self.compute_s, self.memory_s, self.collective_s)
        ideal = self.model_flops / (self.chips * chip["flops"])
        self.roofline_fraction = ideal / bound if bound > 0 else 0.0
        return self

    def to_json(self) -> str:
        return json.dumps(asdict(self), default=float)


def analyze(*, arch, shape, mesh_desc, chips, cost, hlo_text, model_flops,
            bytes_per_device=0.0, note="") -> Roofline:
    """cost: compiled.cost_analysis() dict (per-partition on SPMD modules —
    we scale to all chips); hlo_text: compiled.as_text()."""
    flops = float(cost.get("flops", 0.0))
    acc_bytes = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(hlo_text)
    r = Roofline(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        hlo_flops=flops * chips,
        hlo_bytes=acc_bytes * chips,
        collective_bytes_per_chip=coll.total_bytes,
        collectives={k: float(v) for k, v in coll.bytes_by_kind.items()},
        collective_counts=dict(coll.count_by_kind),
        model_flops=model_flops,
        bytes_per_device=bytes_per_device,
        note=note,
    )
    return r.finalize()
