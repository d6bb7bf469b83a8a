"""Decode attention (Sq=1, GQA, ragged KV) as a Pallas TPU kernel.

The prefill-shaped ``kernels/flash_attention.py`` wastes its whole
(Sq/block_q) grid axis on decode, where every slot contributes exactly one
query token.  This kernel is shaped for the serving fast path instead:

  * grid = (batch, kv_heads, Sk/block_k) — no query axis at all.  The KV
    dimension is the innermost 'arbitrary' axis so the online-softmax
    accumulators live in VMEM scratch across KV steps.
  * GQA is handled *inside* the kernel: the query block is the [G, D]
    group of heads sharing one KV head, so the [B, 1, H, D] query never
    replicates K/V and the per-step matmuls are [G, D] x [D, block_k].
  * ragged batches: ``kv_len`` is a per-slot [B] vector scalar-prefetched
    to SMEM.
    Whole KV blocks past a slot's live length are skipped with ``pl.when``
    (zero compute for the dead cache tail — continuous batching leaves
    every slot at a different fill level), partial blocks are masked.

K/V are read in the cache's own kv-head-major layout, [K, B, Sk, D], so
every (1, 1, block_k, D) block ends in (block_k, D) as Mosaic's tiling
needs, and the decode step never transposes the cache.  The cache may
be the whole layer stack, [L, K, B, Sk, D]: the layer to read rides the
scalar-prefetch operand after the ``kv_len``s and steers the K/V index
maps, so a decode step that carries the stacked cache through its layer
loop never slices a layer out of it.  Non-dividing Sk is handled by
zero-padding one layer's K/V up to a block multiple in the wrapper; the
pad region sits beyond every ``kv_len`` so the masking covers it.  The
grid divisibility is asserted after padding (expolint pallas-rules).

``decode_attention_paged`` is the same online-softmax loop over a *paged*
KV pool: K/V live as [K, num_pages, page_size, D] pages shared by all
slots, and each slot's page table row is scalar-prefetched to SMEM so the
BlockSpec index maps can steer the K/V DMA through it — the kernel reads
exactly the pages a slot owns, never a dense [B, Smax] stripe.  The grid
is (batch, kv_heads, pages-per-slot); whole pages past ``kv_len`` are
skipped with ``pl.when`` and the final partial page is masked by kpos.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, k_start, kv_len):
    """Fold one [bk, D] K/V block into the [G, Dv] accumulators."""
    q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [G, bk]
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]              # [G, 1]
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


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_k: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    pl.when(ik == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    kv_len = meta_ref[b]
    k_start = ik * block_k
    pl.when(k_start < kv_len)(lambda: _online_softmax_step(
        q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale=scale,
        k_start=k_start, kv_len=kv_len))
    pl.when(ik == nk - 1)(lambda: _finalize(o_ref, l_scr, acc_scr))


def _scratch(G: int, Dv: int):
    return [pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32)]


def decode_attention(q, k, v, kv_len, layer=0, *,
                     scale: float | None = None, block_k: int = 128,
                     interpret: bool = False):
    """q: [B, H, D]; k: [L, K, B, Sk, D] (or one layer's [K, B, Sk, D]);
    v: the same with Dv; kv_len: [B] int32 (per-slot live cache length,
    position p attended iff p < kv_len); layer: int32 scalar, the layer
    of a stacked k/v to attend to.  Returns [B, H, Dv]."""
    if k.ndim == 4:
        k, v = k[None], v[None]
    Bsz, H, D = q.shape
    K, Sk = k.shape[1], k.shape[3]
    Dv = v.shape[-1]
    assert H % K == 0, (H, K)
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    block_k = min(block_k, Sk)
    pad = -Sk % block_k
    if pad:
        # padded tail sits at kpos >= Sk >= every kv_len -> fully masked
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(jax.lax.dynamic_index_in_dim(k, layer, 0), widths)
        v = jnp.pad(jax.lax.dynamic_index_in_dim(v, layer, 0), widths)
        layer = 0
    Skp = Sk + pad
    assert Skp % block_k == 0, (Skp, block_k)
    grid = (Bsz, K, Skp // block_k)

    qg = q.reshape(Bsz, K, G, D)
    # kv_len [B] then the layer: one operand, so the call keeps its four
    meta = jnp.concatenate([jnp.asarray(kv_len, jnp.int32).reshape(Bsz),
                            jnp.asarray(layer, jnp.int32).reshape(1)])
    kernel = functools.partial(_kernel, scale=scale, block_k=block_k)

    # a block past the slot's live rows maps to its last live one, so the
    # pipeline fetches nothing for it (pl.when skips its compute)
    kv_block = lambda b, h, ik, meta: (
        meta[Bsz], h, b,
        jnp.minimum(ik, jnp.maximum(meta[b] - 1, 0) // block_k), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # kv_len and the layer land in SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, meta: (b, h, 0, 0)),
            pl.BlockSpec((None, 1, 1, block_k, D), kv_block),
            pl.BlockSpec((None, 1, 1, block_k, Dv), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, ik, meta: (b, h, 0, 0)),
        scratch_shapes=_scratch(G, Dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bsz, K, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(meta, qg, k, v)
    return out.reshape(Bsz, H, Dv)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    npg = pl.num_programs(2)
    pl.when(ip == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    kv_len = len_ref[b]
    k_start = ip * page_size
    pl.when(k_start < kv_len)(lambda: _online_softmax_step(
        q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale=scale,
        k_start=k_start, kv_len=kv_len))
    pl.when(ip == npg - 1)(lambda: _finalize(o_ref, l_scr, acc_scr))


def decode_attention_paged(q, k_pool, v_pool, page_table, kv_len, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """Sq=1 GQA decode attention against a paged KV pool.

    q: [B, H, D]; k_pool: [K, P, ps, D]; v_pool: [K, P, ps, Dv];
    page_table: [B, W] int32 (physical page backing each slot's logical
    page — prefetched to SMEM and read by the K/V index maps, so only a
    slot's own pages are ever DMA'd); kv_len: [B] int32 (position p
    attended iff p < kv_len; stale rows of partially-filled or
    unallocated pages are masked).  The page dimension is the innermost
    'arbitrary' grid axis — no ``//`` feeds the grid, the page-table
    width *is* the page count.  Returns [B, H, Dv]."""
    Bsz, H, D = q.shape
    K, P, page_size = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    Dv = v_pool.shape[-1]
    assert H % K == 0, (H, K)
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    W = page_table.shape[1]
    grid = (Bsz, K, W)

    qg = q.reshape(Bsz, K, G, D)
    # unmapped entries hold an out-of-range sentinel; clamp so the K/V
    # index maps never DMA past the pool (the rows are masked anyway)
    pt = jnp.minimum(jnp.asarray(page_table, jnp.int32), P - 1)
    lens = jnp.asarray(kv_len, jnp.int32)
    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # page_table + kv_len land in SMEM
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, ip, pt, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, ip, pt, lens: (h, pt[b, ip], 0, 0)),
            pl.BlockSpec((1, 1, page_size, Dv),
                         lambda b, h, ip, pt, lens: (h, pt[b, ip], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, ip, pt, lens: (b, h, 0, 0)),
        scratch_shapes=_scratch(G, Dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bsz, K, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pt, lens, qg, k_pool, v_pool)
    return out.reshape(Bsz, H, Dv)
