"""Flash attention (GQA) as a Pallas TPU kernel.

TPU-native design (not a CUDA port):
  * grid = (batch, q_heads, Sq/block_q, Sk/block_k); the KV dimension is the
    innermost 'arbitrary' grid axis so the online-softmax accumulators live
    in VMEM scratch across KV steps (TPU has no cross-core shared memory —
    the accumulation pattern replaces the CUDA warp-level reduction).
  * the kernel reads head-major [B, H|K, S, D] operands (the wrapper
    transposes the model's [B, S, H|K, D] activations), so the last two
    dims of every (1, 1, block, D) block are (block, D): Mosaic tiles
    those onto (8, 128) vregs, which a size-1 slice of a head axis in
    the second-minor position cannot satisfy.  The working set
    (~2·block·D + block_q·block_k fp32) stays well under 16 MB VMEM for
    128x128 blocks at D<=256.
  * block_q/block_k default to 128 — MXU-aligned (128x128 systolic array).
  * GQA: the KV head index is derived in the index_map (h // group) so no
    K/V replication is materialised.
  * causal: whole KV blocks strictly above the diagonal are skipped with
    pl.when (zero compute), partial blocks are masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_major(x):
    """[B, S, H, D] <-> [B, H, S, D]."""
    return jnp.swapaxes(x, 1, 2)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            q_offset: int, kv_len: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]              # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, Dv]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip whole blocks strictly above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B, Sq, H, D]; k, v: [B, Sk, K, Dk/Dv] -> [B, Sq, H, Dv]."""
    Bsz, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    assert H % K == 0, (H, K)
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    grid = (Bsz, H, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, kv_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(_head_major(q), _head_major(k), _head_major(v))
    return _head_major(out)
