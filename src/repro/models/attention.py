"""GQA/MQA attention with qk-norm, partial/interleaved RoPE, and a decode
path against a pre-allocated KV cache.

The KV cache is kv-head-major — dense ``[K, B, max_seq, hd]`` stripes or a
paged ``[K, num_pages, page_size, hd]`` pool — the layout the Pallas decode
kernels read, so a decode step writes its new rows in place and never
transposes the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import rmsnorm
from repro.models.params import Param
from repro.models.rope import apply_rope
from repro.sharding.rules import shard


def make_attention(cfg):
    d = cfg.d_model
    p = {
        "wq": Param((d, cfg.q_dim), ("embed", "heads"), init="scaled"),
        "wk": Param((d, cfg.kv_dim), ("embed", "kv_heads"), init="scaled"),
        "wv": Param((d, cfg.kv_dim), ("embed", "kv_heads"), init="scaled"),
        "wo": Param((cfg.q_dim, d), ("heads", "embed"), init="scaled"),
    }
    if cfg.qk_norm:
        p["q_norm"] = Param((cfg.head_dim,), (None,), init="ones")
        p["k_norm"] = Param((cfg.head_dim,), (None,), init="ones")
    return p


def _qkv(cfg, p, x, positions):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    rd = cfg.rotary_dim
    if rd:
        q = apply_rope(q, positions, theta=cfg.rope_theta, rotary_dim=rd,
                       interleaved=cfg.rope_interleaved)
        k = apply_rope(k, positions, theta=cfg.rope_theta, rotary_dim=rd,
                       interleaved=cfg.rope_interleaved)
    return q, k, v


def apply_attention(cfg, p, x, positions):
    """Full-sequence causal attention (train / prefill).

    x: [B, S, d]; positions: [S] or [B, S]. Returns ([B, S, d], (k, v))."""
    q, k, v = _qkv(cfg, p, x, positions)
    q = shard(q, "batch", "seq", None, None)
    k = shard(k, "batch", "seq_kv", None, None)
    out = ops.flash_attention(q, k, v, causal=True)
    out = out.reshape(*x.shape[:2], cfg.q_dim)
    out = shard(out, "batch", "seq", "heads")
    return out @ p["wo"], (_kv_major(k), _kv_major(v))


def _kv_major(x):
    """Move the kv-head axis of [B, (S,) K, hd] rows to the front, the
    cache's layout."""
    return jnp.moveaxis(x, -2, 0)


def make_kv_cache(cfg, batch: int, max_seq: int, stack: tuple = ()):
    """Descriptor tree for the KV cache (materialise with init_params or
    abstract_params)."""
    lead = tuple(stack)
    lead_logical = (None,) * len(lead)
    shape = (*lead, cfg.num_kv_heads, batch, max_seq, cfg.head_dim)
    logical = (*lead_logical, "kv_heads", "batch", "seq_kv", None)
    return {
        "k": Param(shape, logical, init="zeros", dtype=cfg.dtype),
        "v": Param(shape, logical, init="zeros", dtype=cfg.dtype),
    }


def make_kv_cache_paged(cfg, num_pages: int, page_size: int,
                        stack: tuple = ()):
    """Descriptor tree for a *paged* KV cache: a pool of
    ``num_pages × page_size`` token rows shared by every slot, indexed
    through per-slot page tables instead of a dense ``batch × max_seq``
    stripe.  No ``batch`` axis — resident memory is decoupled from
    slots × max_seq."""
    lead = tuple(stack)
    lead_logical = (None,) * len(lead)
    shape = (*lead, cfg.num_kv_heads, num_pages, page_size, cfg.head_dim)
    logical = (*lead_logical, "kv_heads", None, "seq_kv", None)
    return {
        "k": Param(shape, logical, init="zeros", dtype=cfg.dtype),
        "v": Param(shape, logical, init="zeros", dtype=cfg.dtype),
    }


def _paged_rows(pool):
    """Flatten [P, ps, ...] pool to [(P*ps), ...] token rows."""
    P, ps = pool.shape[0], pool.shape[1]
    return pool.reshape(P * ps, *pool.shape[2:])


def paged_write_rows(pool, page_table, positions, values, active=None):
    """Scatter per-token rows through a page table.

    pool: [P, ps, ...]; page_table: [B, W] int32; positions: [B] or
    [B, C] int32 logical token positions; values: rows matching
    ``positions`` with trailing dims of the pool; active: optional [B]
    bool — inactive slots' writes are dropped (their stale table entries
    may point at pages now owned by other slots, so the drop is a
    correctness requirement, not an optimisation)."""
    P, ps = pool.shape[0], pool.shape[1]
    W = page_table.shape[1]
    B = page_table.shape[0]
    logical_pg = jnp.clip(positions // ps, 0, W - 1)
    if positions.ndim == 1:
        phys = page_table[jnp.arange(B), logical_pg]            # [B]
        amask = active if active is not None else None
    else:
        phys = page_table[jnp.arange(B)[:, None], logical_pg]   # [B, C]
        amask = active[:, None] if active is not None else None
    flat = phys * ps + positions % ps
    if amask is not None:
        flat = jnp.where(amask, flat, P * ps)   # out of range -> dropped
    rows = _paged_rows(pool).at[flat].set(values, mode="drop")
    return rows.reshape(pool.shape)


def paged_write_kv(pool, page_table, positions, values, active=None):
    """``paged_write_rows`` per KV head: pool [K, P, ps, hd]; values
    [B, (C,) K, hd]."""
    return jax.vmap(paged_write_rows, in_axes=(0, None, None, -2, None))(
        pool, page_table, positions, values, active)


def apply_attention_decode_paged(cfg, p, x, cache, pos, page_table,
                                 active=None):
    """One-token decode against the paged pool.  x: [B, 1, d]; cache:
    {k,v: [K, P, ps, hd]}; pos: [B] int32; page_table: [B, W] int32
    (traced — constant within a fused sync, updated by the engine's
    allocator between syncs); active: optional [B] bool.
    Returns (out, new_cache)."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x, pos[:, None])
    k = paged_write_kv(cache["k"], page_table, pos, k_new[:, 0], active)
    v = paged_write_kv(cache["v"], page_table, pos, v_new[:, 0], active)
    out = ops.decode_attention_paged(q[:, 0], k, v, page_table, pos + 1,
                                     scale=cfg.head_dim ** -0.5)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], {"k": k, "v": v}


def apply_attention_prefill_chunk_paged(cfg, p, x, cache, start, page_table,
                                        active=None):
    """Batched C-token prefill through the page table.  Same contract as
    ``apply_attention_prefill_chunk`` with the dense stripe replaced by
    the pool: KV rows scatter to ``table[b, pos//ps]*ps + pos%ps`` and
    the chunk attends to the slot's gathered pages under the usual
    kpos <= start+q mask (stale rows of unwritten pages sit beyond the
    mask).  Returns (out [B, C, d], new_cache)."""
    from repro.kernels.ref import gather_kv_pages

    B, C, _ = x.shape
    positions = start[:, None] + jnp.arange(C)[None, :]         # [B, C]
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    k = paged_write_kv(cache["k"], page_table, positions, k_new, active)
    v = paged_write_kv(cache["v"], page_table, positions, v_new, active)
    kg = gather_kv_pages(k, page_table)                # [K, B, W*ps, hd]
    vg = gather_kv_pages(v, page_table)
    smax = kg.shape[2]
    K = kg.shape[0]
    G = cfg.num_heads // K
    qg = q.reshape(B, C, K, G, cfg.head_dim).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,kbsd->bkgqs", qg, kg.astype(jnp.float32))
    scores = scores * (cfg.head_dim ** -0.5)
    mask = jnp.arange(smax)[None, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,kbsd->bqkgd", probs, vg.astype(jnp.float32))
    out = out.reshape(B, C, cfg.q_dim).astype(x.dtype)
    return out @ p["wo"], {"k": k, "v": v}


def apply_attention_prefill_chunk(cfg, p, x, cache, start, active=None):
    """Batched prefill of a C-token chunk into the KV cache.

    x: [B, C, d]; cache: {k,v: [K, B, Smax, hd]}; start: [B] int32 (cache
    position of the chunk's first token — per-slot, so freshly admitted
    requests prefill while resident slots sit at different fill levels);
    active: optional [B] bool — inactive slots leave the cache untouched
    and their outputs are garbage (callers must ignore them).

    This is ``flash_attention(q_offset=...)`` generalised to a *traced
    per-slot* offset vector: chunk queries attend to the full cache with a
    kpos <= start+q mask.  Returns (out [B, C, d], new_cache)."""
    B, C, _ = x.shape
    positions = start[:, None] + jnp.arange(C)[None, :]         # [B, C]
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    smax = cache["k"].shape[2]
    wpos = positions if active is None else jnp.where(
        active[:, None], positions, smax)
    b_idx = jnp.arange(B)[:, None]
    k = cache["k"].at[:, b_idx, wpos].set(_kv_major(k_new), mode="drop")
    v = cache["v"].at[:, b_idx, wpos].set(_kv_major(v_new), mode="drop")
    K = k.shape[0]
    G = cfg.num_heads // K
    qg = q.reshape(B, C, K, G, cfg.head_dim).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,kbsd->bkgqs", qg, k.astype(jnp.float32))
    scores = scores * (cfg.head_dim ** -0.5)
    mask = jnp.arange(smax)[None, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,kbsd->bqkgd", probs, v.astype(jnp.float32))
    out = out.reshape(B, C, cfg.q_dim).astype(x.dtype)
    return out @ p["wo"], {"k": k, "v": v}


def apply_attention_decode(cfg, p, x, cache, pos, active=None):
    """One-token decode. x: [B, 1, d]; cache: {k,v: [K, B, Smax, hd]};
    pos: [B] int32 (index of the new token); active: optional [B] bool —
    inactive slots leave the cache untouched (continuous batching).
    Returns (out, new_cache)."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x, pos[:, None])
    b_idx = jnp.arange(B)
    smax = cache["k"].shape[2]
    wpos = pos if active is None else jnp.where(active, pos, smax)
    k = cache["k"].at[:, b_idx, wpos].set(_kv_major(k_new[:, 0]),
                                          mode="drop")
    v = cache["v"].at[:, b_idx, wpos].set(_kv_major(v_new[:, 0]),
                                          mode="drop")
    # position p attended iff p <= pos, i.e. p < pos + 1 == kv_len.  The
    # dispatcher's ref path is bit-identical to the previous inline einsum
    # formulation; on TPU / REPRO_PALLAS=interpret the Sq=1 Pallas decode
    # kernel skips the dead cache tail per slot.
    out = ops.decode_attention(q[:, 0], k, v, pos + 1,
                               scale=cfg.head_dim ** -0.5)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], {"k": k, "v": v}
