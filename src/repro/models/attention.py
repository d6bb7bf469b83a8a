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


def _chunk_attention(cfg, q, k, v, positions):
    """A chunk's queries against a slot's cache rows, fp32 softmax.

    q: [B, C, H, hd]; k, v: [K, B, Smax, hd] (a dense layer or a page
    table's gathered view); positions: [B, C], query i of slot b sees
    cache row p iff p <= positions[b, i].  The dots batch over (kv head,
    slot), the cache's own leading axes, so they read it as it lies.
    Returns [B, C, H * hd] fp32."""
    B, C = positions.shape
    K, smax, hd = k.shape[0], k.shape[2], k.shape[3]
    G = cfg.num_heads // K
    qg = q.reshape(B, C, K, G, hd).transpose(2, 0, 3, 1, 4)  # [K,B,G,C,hd]
    qg = qg.reshape(K, B, G * C, hd).astype(jnp.float32)
    scores = jax.lax.dot_general(qg, k.astype(jnp.float32),
                                 (((3,), (3,)), ((0, 1), (0, 1))))
    scores = scores.reshape(K, B, G, C, smax) * (hd ** -0.5)
    mask = jnp.arange(smax)[None, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[None, :, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).reshape(K, B, G * C, smax)
    out = jax.lax.dot_general(probs, v.astype(jnp.float32),
                              (((3,), (2,)), ((0, 1), (0, 1))))
    out = out.reshape(K, B, G, C, hd).transpose(1, 3, 0, 2, 4)
    return out.reshape(B, C, cfg.q_dim)


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
    out = _chunk_attention(cfg, q, gather_kv_pages(k, page_table),
                           gather_kv_pages(v, page_table), positions)
    return out.astype(x.dtype) @ p["wo"], {"k": k, "v": v}


def _write_rows(buf, rows, layer, start, active):
    """Write each slot's new rows into the cache buffer, in place.

    buf: [K, B, Smax, hd], or with ``layer`` (an int32 scalar) the stack
    [L, K, B, Smax, hd] written at that layer; rows: [K, B, C, hd], slot
    b's rows for positions start[b] .. start[b]+C-1; active: optional [B]
    bool.  Rows of an inactive slot, and rows at or past Smax, are
    dropped.  Both forms keep the buffer in its own layout, so XLA updates
    it where it lies:

    * one row a slot (decode): one scatter whose window is one head_dim
      row, its indices naming layer, KV head, slot and position.  With
      the KV heads in the window XLA lays a carried stack out slot-major
      and copies it whole to feed the kernel.
    * a chunk: one ``dynamic_update_slice`` of the slot's [K, C, hd]
      block per slot.  A scatter costs the chip per index, K·B·C of them
      (37 of a 134 ms chunk at qwen3-4b's widths on a TPU v5e).  The
      update clamps its start, so a block that would reach past Smax
      lands lower and its rows before ``start`` write back what they
      read."""
    at = () if layer is None else (layer,)
    K, B, smax, hd = buf.shape[-4:]
    C = rows.shape[2]
    if C == 1:
        pos = start[:, None] if active is None else jnp.where(
            active[:, None], start[:, None], smax)
        return buf.at[(*at, jnp.arange(K)[:, None, None],
                       jnp.arange(B)[None, :, None], pos[None])].set(
                           rows, mode="drop")
    lo = jnp.clip(start, 0, smax - C)
    size = (1,) * len(at) + (K, 1, C, hd)
    for b in range(B):
        idx = (*at, 0, b, lo[b], 0)
        old = jax.lax.dynamic_slice(buf, idx, size).reshape(K, C, hd)
        src = jnp.arange(C) - (start[b] - lo[b])    # row of ``rows`` here
        new = jnp.take(rows[:, b], jnp.clip(src, 0, C - 1), axis=1)
        ok = src >= 0 if active is None else active[b] & (src >= 0)
        blk = jnp.where(ok[None, :, None], new, old)
        buf = jax.lax.dynamic_update_slice(buf, blk.reshape(size), idx)
    return buf


def apply_attention_prefill_chunk(cfg, p, x, cache, start, active=None,
                                  layer=None):
    """Batched prefill of a C-token chunk into the KV cache.

    x: [B, C, d]; cache: {k,v: [K, B, Smax, hd]}, or with ``layer`` (an
    int32 scalar) the whole stack {k,v: [L, K, B, Smax, hd]}, of which
    layer ``layer`` is written and read in place; start: [B] int32 (cache
    position of the chunk's first token — per-slot, so freshly admitted
    requests prefill while resident slots sit at different fill levels);
    active: optional [B] bool — inactive slots leave the cache untouched
    and their outputs are garbage (callers must ignore them).  Rows at or
    past Smax are dropped.

    This is ``flash_attention(q_offset=...)`` generalised to a *traced
    per-slot* offset vector: chunk queries attend to the full cache with a
    kpos <= start+q mask.  Returns (out [B, C, d], new_cache)."""
    B, C, _ = x.shape
    positions = start[:, None] + jnp.arange(C)[None, :]         # [B, C]
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    new = {"k": _write_rows(cache["k"], _kv_major(k_new), layer, start,
                            active),
           "v": _write_rows(cache["v"], _kv_major(v_new), layer, start,
                            active)}
    k, v = new["k"], new["v"]
    if layer is not None:
        k, v = (jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
                for c in (k, v))
    out = _chunk_attention(cfg, q, k, v, positions)
    return out.astype(x.dtype) @ p["wo"], new


def apply_attention_decode(cfg, p, x, cache, pos, active=None, layer=None):
    """One-token decode. x: [B, 1, d]; cache: {k,v: [K, B, Smax, hd]}, or
    with ``layer`` (an int32 scalar) the whole stack {k,v: [L, K, B, Smax,
    hd]}, of which layer ``layer`` is written in place and read by the
    kernel through its index maps; pos: [B] int32 (index of the new
    token); active: optional [B] bool — inactive slots leave the cache
    untouched (continuous batching).  Returns (out, new_cache)."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x, pos[:, None])
    k = _write_rows(cache["k"], _kv_major(k_new), layer, pos, active)
    v = _write_rows(cache["v"], _kv_major(v_new), layer, pos, active)
    # position p attended iff p <= pos, i.e. p < pos + 1 == kv_len.  The
    # dispatcher's ref path is bit-identical to the previous inline einsum
    # formulation; on TPU / REPRO_PALLAS=interpret the Sq=1 Pallas decode
    # kernel skips the dead cache tail per slot.
    out = ops.decode_attention(q[:, 0], k, v, pos + 1,
                               0 if layer is None else layer,
                               scale=cfg.head_dim ** -0.5)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], {"k": k, "v": v}
