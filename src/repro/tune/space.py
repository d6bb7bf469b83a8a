"""Per-kernel tunable search spaces for the autotuner.

Each Pallas kernel declares a :class:`KernelSpec`: the shape axes that
identify a workload, the tunable knobs with their candidate values, the
current dispatch defaults (``kernels/ops.py`` falls back to these on a
cache miss, so ``defaults`` here must mirror the ops-layer constants),
and a static validity predicate mirroring the kernels' divisibility
asserts — invalid configs are excluded from the grid instead of crashing
clients mid-sweep.

Hardness for the domino partial order is the **predicted cost**: a
roofline estimate (FLOPs / HBM bytes / per-grid-cell launch overhead,
same hardware model as ``launch/roofline.py``) collapsed to a single
scalar.  That makes the order total, which is exactly the
JobPruner-style "learned predictor pre-orders the grid" shape from
PAPERS.md: one config timing out prunes every config predicted to be at
least as expensive.  The same estimate drives ``sim_duration`` when the
sweep runs on the simulator engine (virtual seconds proportional to
predicted microseconds), so the paper's timeout/domino machinery applies
unchanged.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass

from repro.core.space import ParamSpace, axis
from repro.launch.roofline import PEAKS, V5E

# hardware model (the v5e peaks of launch/roofline.py) + a per-grid-cell
# launch overhead term — the block knobs trade this overhead against
# memory traffic, which is the whole tuning surface
CELL_OVERHEAD_US = 0.2        # per pallas grid cell

# virtual seconds per predicted microsecond when the sweep runs on the
# simulator engine (pure scale factor: timeouts are k x incumbent in the
# same unit, so the choice only affects readability of the virtual clock)
SIM_SECONDS_PER_US = 0.05

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclass(frozen=True)
class KernelSpec:
    """Tunable surface of one kernel (see module docstring)."""

    name: str
    shape_axes: tuple               # ordered workload-identity fields
    smoke_shape: dict               # small CI shape
    full_shape: dict                # representative shape
    defaults: dict                  # tunable -> current dispatch default
    tunables: dict                  # tunable -> candidate values
    pathological: dict              # tunable -> adversarially bad values

    @property
    def tunable_names(self) -> tuple:
        return tuple(self.tunables)


SPECS: dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(
        name="flash_attention",
        shape_axes=("b", "s", "h", "kvh", "d"),
        smoke_shape={"b": 1, "s": 256, "h": 4, "kvh": 2, "d": 64},
        full_shape={"b": 1, "s": 1024, "h": 8, "kvh": 2, "d": 64},
        defaults={"block_q": 128, "block_k": 128},
        tunables={"block_q": (64, 128, 256), "block_k": (64, 128, 256)},
        pathological={"block_q": (8, 16), "block_k": (8, 16)},
    ),
    "ssd_scan": KernelSpec(
        name="ssd_scan",
        shape_axes=("b", "s", "h", "p", "g", "n"),
        smoke_shape={"b": 1, "s": 512, "h": 2, "p": 64, "g": 1, "n": 32},
        full_shape={"b": 1, "s": 2048, "h": 4, "p": 64, "g": 1, "n": 64},
        defaults={"chunk": 64},
        # >= 3 pathological values: with max_clients concurrent timeouts
        # at least one is still queued when the first fires, so the
        # domino rule provably prunes (not just times out) on the
        # adversarial grid
        tunables={"chunk": (32, 64, 128, 256)},
        pathological={"chunk": (2, 4, 8)},
    ),
    "decode_attention": KernelSpec(
        name="decode_attention",
        shape_axes=("b", "sk", "h", "kvh", "d"),
        smoke_shape={"b": 4, "sk": 512, "h": 4, "kvh": 2, "d": 64},
        full_shape={"b": 16, "sk": 2048, "h": 8, "kvh": 2, "d": 64},
        defaults={"block_k": 512},
        tunables={"block_k": (64, 128, 256, 512)},
        pathological={"block_k": (8, 16)},
    ),
    "decode_attention_paged": KernelSpec(
        name="decode_attention_paged",
        shape_axes=("b", "sk", "kvh", "g", "d"),
        smoke_shape={"b": 4, "sk": 256, "kvh": 2, "g": 2, "d": 64},
        full_shape={"b": 16, "sk": 2048, "kvh": 2, "g": 4, "d": 64},
        defaults={"page_size": 16},
        tunables={"page_size": (8, 16, 32, 64, 128)},
        pathological={"page_size": (1, 2)},
    ),
}


# ---------------------------------------------------------------------------
# static validity (mirrors the kernels' divisibility asserts)
# ---------------------------------------------------------------------------
def valid(kernel: str, cell: dict) -> bool:
    """True iff the config satisfies the kernel's static constraints —
    mirrored from the kernels' own divisibility asserts so bad configs
    are rejected before any client process touches them."""
    if kernel == "flash_attention":
        s = cell["s"]
        bq, bk = min(cell["block_q"], s), min(cell["block_k"], s)
        return bq > 0 and bk > 0 and s % bq == 0 and s % bk == 0
    if kernel == "ssd_scan":
        s, c = cell["s"], min(cell["chunk"], cell["s"])
        return c > 0 and s % c == 0
    if kernel == "decode_attention":
        # the wrapper zero-pads Sk up to a block multiple, so any
        # positive block is statically valid (padding waste is costed)
        return cell["block_k"] > 0
    if kernel == "decode_attention_paged":
        return 0 < cell["page_size"] <= cell["sk"]
    raise KeyError(f"unknown kernel {kernel!r} (have {sorted(SPECS)})")


# ---------------------------------------------------------------------------
# predicted cost (roofline estimate, microseconds)
# ---------------------------------------------------------------------------
def predicted_cost_us(kernel: str, cell: dict) -> float:
    """Roofline cost estimate in microseconds for one kernel call.

    compute = FLOPs / peak, memory = HBM bytes (including block-dependent
    K/V re-reads and padding waste), overhead = grid cells x launch cost.
    Monotone in the right directions: tiny blocks blow up the overhead
    and re-read terms, huge chunks blow up the intra-chunk quadratic
    term — which is what makes it a usable hardness ordering.
    """
    eb = _DTYPE_BYTES.get(cell.get("dtype", "float32"), 4)
    if kernel == "flash_attention":
        b, s, h, kvh, d = (cell[k] for k in ("b", "s", "h", "kvh", "d"))
        bq = min(cell["block_q"], s)
        bk = min(cell["block_k"], s)
        nq, nk = _ceil_div(s, bq), _ceil_div(s, bk)
        flops = 4.0 * b * s * s * h * d * 0.5          # causal halves it
        qo_bytes = 2.0 * b * s * h * d * eb
        kv_bytes = 2.0 * b * s * kvh * d * eb * nq     # re-read per q row
        cells = b * h * nq * nk
    elif kernel == "ssd_scan":
        b, s, h, p, g, n = (cell[k] for k in
                            ("b", "s", "h", "p", "g", "n"))
        length = min(cell["chunk"], s)
        nc = _ceil_div(s, length)
        flops = b * h * nc * (2.0 * length * length * (n + p)
                              + 4.0 * length * n * p)
        qo_bytes = 2.0 * b * s * h * p * eb + 2.0 * b * s * g * 2 * n * eb
        kv_bytes = b * h * nc * p * n * 4 * 2.0        # fp32 state traffic
        cells = b * h * nc
    elif kernel == "decode_attention":
        b, sk, h, kvh, d = (cell[k] for k in ("b", "sk", "h", "kvh", "d"))
        bk = min(cell["block_k"], sk)
        nk = _ceil_div(sk, bk)
        skp = nk * bk                                  # padding waste
        flops = 4.0 * b * sk * h * d
        qo_bytes = 2.0 * b * h * d * eb
        kv_bytes = 2.0 * b * skp * kvh * d * eb
        cells = b * kvh * nk
    elif kernel == "decode_attention_paged":
        b, sk, kvh, g, d = (cell[k] for k in ("b", "sk", "kvh", "g", "d"))
        ps = cell["page_size"]
        w = _ceil_div(sk, ps)
        flops = 4.0 * b * sk * kvh * g * d
        qo_bytes = 2.0 * b * kvh * g * d * eb
        kv_bytes = 2.0 * b * w * ps * kvh * d * eb
        cells = b * kvh * w
    else:
        raise KeyError(f"unknown kernel {kernel!r} (have {sorted(SPECS)})")
    chip = PEAKS[V5E]
    return (flops / chip["flops"] * 1e6
            + (qo_bytes + kv_bytes) / chip["hbm_bw"] * 1e6
            + cells * CELL_OVERHEAD_US)


def hardness_of(kernel: str, cell: dict) -> tuple:
    """1-tuple hardness: predicted cost.  A total order — one timeout
    domino-prunes everything predicted at least as expensive."""
    return (predicted_cost_us(kernel, cell),)


def sim_duration_s(kernel: str, cell: dict) -> float:
    """Virtual runtime on the simulator engine (predicted microseconds
    scaled to virtual seconds)."""
    return predicted_cost_us(kernel, cell) * SIM_SECONDS_PER_US


# ---------------------------------------------------------------------------
# grid construction
# ---------------------------------------------------------------------------
def candidate_values(spec: KernelSpec, shape: dict, *, adversarial: int = 0,
                     seed: int = 0) -> dict:
    """Per-tunable candidate lists: the declared candidates filtered for
    static validity against ``shape`` (defaults always included), plus
    ``adversarial`` seeded draws from the pathological pool — the
    deliberately bad configs the CI smoke grid uses to prove the
    domino/timeout rule fires."""
    rnd = random.Random(seed)
    out = {}
    for name, cands in spec.tunables.items():
        vals = list(dict.fromkeys((spec.defaults[name], *cands)))
        if adversarial:
            pool = list(spec.pathological.get(name, ()))
            rnd.shuffle(pool)
            vals.extend(pool[:adversarial])
        kept = []
        for v in vals:
            cell = {**shape, **spec.defaults, name: v}
            if valid(spec.name, cell):
                kept.append(v)
        out[name] = tuple(dict.fromkeys(kept))
    return out


def build_space(kernel: str, shape: dict | None = None, *, smoke: bool = False,
                dtype: str = "float32", adversarial: int = 0,
                seed: int = 0) -> ParamSpace:
    """The sweep grid for one kernel: shape fields are fixed single-value
    axes (they appear in the results table, so every row is
    self-describing), tunables are real axes.  Cross-knob validity is
    enforced with a dependent domain on the last tunable axis, so the
    expanded grid contains no statically-invalid cell."""
    spec = SPECS[kernel]
    shape = dict(shape or (spec.smoke_shape if smoke else spec.full_shape))
    missing = [a for a in spec.shape_axes if a not in shape]
    if missing:
        raise ValueError(f"shape for {kernel} is missing axes {missing}")
    cands = candidate_values(spec, {**shape, "dtype": dtype},
                             adversarial=adversarial, seed=seed)
    axes: dict = {a: (shape[a],) for a in spec.shape_axes}
    axes["dtype"] = (dtype,)
    names = list(spec.tunable_names)
    for name in names[:-1]:
        axes[name] = axis(cands[name])
    last = names[-1]

    def _last_domain(cell, _k=kernel, _last=last, _vals=cands[last]):
        return tuple(v for v in _vals if valid(_k, {**cell, _last: v}))

    axes[last] = axis(_last_domain)
    return ParamSpace.grid(**axes)


def next_pow2(v: int) -> int:
    return 1 << max(0, math.ceil(math.log2(v))) if v > 1 else 1


__all__ = ["KernelSpec", "SPECS", "valid", "predicted_cost_us",
           "hardness_of", "sim_duration_s", "candidate_values",
           "build_space", "next_pow2", "SIM_SECONDS_PER_US"]
