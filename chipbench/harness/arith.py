"""The roofline: how near the chip's peaks a kernel's device time came.

The operations and bytes that a kernel's work needs come from the
configuration's reference module (``reference/``), which keeps the
arithmetic of its architecture beside its plain forward pass; the peaks
come from ``chip.PEAKS``.
"""
from __future__ import annotations


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Share (%) of the roofline that ``seconds`` of device time reached,
    and which bound applies: the least time is the larger of operations
    over peak operations and bytes over peak bandwidth."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
