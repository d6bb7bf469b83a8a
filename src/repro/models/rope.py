"""Rotary position embeddings: full (llama), partial/interleaved (GLM 2d),
and YaRN-scaled frequencies (DeepSeek-V2)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _angles(positions, rotary_dim: int, theta: float, inv_freq=None):
    """positions [...,S] -> [..., S, rotary_dim//2] angles (fp32)."""
    half = rotary_dim // 2
    inv = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
           if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    return positions.astype(jnp.float32)[..., None] * inv


def get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor: 0.1·mscale·ln(scale) + 1, and 1
    where the positions are not scaled."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(dim: int, theta: float, orig: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the first frequency index that is interpolated at all
    and the first that is interpolated fully.  c(r) is the index whose
    wavelength fits r times into ``orig`` positions."""
    c = lambda r: dim * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))
    return (max(math.floor(c(beta_fast)), 0),
            min(math.ceil(c(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float, orig: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim // 2] rotary frequencies blended from the original ones
    (f_extra, kept where many wavelengths fit in ``orig``) and the
    interpolated ones (f_extra / factor)."""
    i = np.arange(dim // 2, dtype=np.float32)
    f_extra = theta ** (-2.0 * i / dim)
    f_inter = f_extra / factor
    low, high = yarn_correction_range(dim, theta, orig, beta_fast, beta_slow)
    if low == high:
        high += 0.001
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return (f_inter * (1.0 - m) + f_extra * m).astype(np.float32)


def apply_rope(x, positions, *, theta: float = 10000.0,
               rotary_dim: int | None = None, interleaved: bool = False,
               inv_freq=None, cos_scale: float = 1.0):
    """x: [B, S, H, D] (or [B, S, D] treated as H=1), positions: [S] or [B, S].

    interleaved=True pairs (0,1),(2,3),... (GLM/chatglm 2d-RoPE);
    False uses the llama half-split convention.
    Only the first ``rotary_dim`` features rotate; the rest pass through.
    ``inv_freq`` ([rotary_dim // 2]) replaces theta's frequencies and
    ``cos_scale`` multiplies cos and sin (YaRN).
    """
    D = x.shape[-1]
    rotary_dim = D if rotary_dim is None else rotary_dim
    if rotary_dim == 0:
        return x
    ang = _angles(positions, rotary_dim, theta, inv_freq)  # [..., S, half]
    # broadcast to [B, S, 1, half] against x [B, S, H, D]
    while ang.ndim < x.ndim:
        ang = ang[..., None, :] if ang.ndim == x.ndim - 1 else ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cos_scale != 1.0:
        cos, sin = cos * cos_scale, sin * cos_scale
    xr, xp = x[..., :rotary_dim].astype(jnp.float32), x[..., rotary_dim:]
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rot = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    else:
        half = rotary_dim // 2
        x1, x2 = xr[..., :half], xr[..., half:]
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1) if rotary_dim < D \
        else rot.astype(x.dtype)
