"""Multi-head Latent Attention (DeepSeek-V2/V3).

Prefill/train: materialise per-head K/V from the compressed latent.
Decode and chunked prefill: *weight-absorbed* path — queries are projected
into the latent space so attention runs directly against the cached
(c_kv, k_pe); the cache is (kv_lora_rank + qk_rope_head_dim) per token
instead of 2·H·head_dim.  Queries come through a compressed latent of
``q_lora_rank`` (DeepSeek-V3) or, with ``q_lora_rank`` 0, one projection
(DeepSeek-V2-Lite); the rope part rotates at YaRN's frequencies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.attention import paged_write_rows, write_rows
from repro.models.layers import rmsnorm
from repro.models.params import Param
from repro.models.rope import apply_rope, get_mscale, yarn_inv_freq
from repro.sharding.rules import shard

# the scope of the part that reads the latent cache: scores, softmax and
# values (a device profile attributes its operations by this name)
LATENT_SCOPE = "mla_latent_attention"


def make_mla(cfg):
    d, m, H = cfg.d_model, cfg.mla, cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        q = {"wdq": Param((d, m.q_lora_rank), ("embed", "q_lora"),
                          init="scaled"),
             "q_norm": Param((m.q_lora_rank,), (None,), init="ones"),
             "wuq": Param((m.q_lora_rank, H * qk_head), ("q_lora", "heads"),
                          init="scaled")}
    else:
        q = {"wq": Param((d, H * qk_head), ("embed", "heads"), init="scaled")}
    return {
        **q,
        "wdkv": Param((d, m.kv_lora_rank), ("embed", "kv_lora"), init="scaled"),
        "wkr": Param((d, m.qk_rope_head_dim), ("embed", None), init="scaled"),
        "kv_norm": Param((m.kv_lora_rank,), (None,), init="ones"),
        "wuk": Param((m.kv_lora_rank, H * m.qk_nope_head_dim),
                     ("kv_lora", "heads"), init="scaled"),
        "wuv": Param((m.kv_lora_rank, H * m.v_head_dim),
                     ("kv_lora", "heads"), init="scaled"),
        "wo": Param((H * m.v_head_dim, d), ("heads", "embed"), init="scaled"),
    }


def _rope(cfg, x, positions):
    """Rotary embedding of the rope part of q or k, at YaRN's frequencies
    (theta's own where ``cfg.yarn_factor`` is 1)."""
    f = cfg.yarn_factor
    inv = yarn_inv_freq(cfg.mla.qk_rope_head_dim, cfg.rope_theta, f,
                        cfg.yarn_original_max_positions, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow)
    return apply_rope(x, positions, theta=cfg.rope_theta,
                      interleaved=cfg.rope_interleaved, inv_freq=inv,
                      cos_scale=get_mscale(f, cfg.yarn_mscale)
                      / get_mscale(f, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg) -> float:
    """qk_head^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    m = cfg.mla
    return ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
            * get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2)


def _queries(cfg, p, x, positions):
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.num_heads
    if m.q_lora_rank:
        q = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps) @ p["wuq"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    return q_nope, _rope(cfg, q_pe, positions)


def _latent_kv(cfg, p, x, positions):
    ckv = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)  # [B,S,r]
    k_pe = _rope(cfg, (x @ p["wkr"])[:, :, None, :], positions)[:, :, 0]
    return ckv, k_pe


def apply_mla(cfg, p, x, positions):
    """Full-sequence MLA (train/prefill). Returns (out, (ckv, k_pe))."""
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.num_heads
    q_nope, q_pe = _queries(cfg, p, x, positions)
    ckv, k_pe = _latent_kv(cfg, p, x, positions)
    k_nope = (ckv @ p["wuk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (ckv @ p["wuv"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (*k_pe.shape[:2], H, m.qk_rope_head_dim))],
        axis=-1,
    )
    q = shard(q, "batch", "seq", None, None)
    k = shard(k, "batch", "seq_kv", None, None)
    out = ops.flash_attention(q, k, v, causal=True, scale=softmax_scale(cfg))
    out = out.reshape(B, S, H * m.v_head_dim)
    out = shard(out, "batch", "seq", "heads")
    return out @ p["wo"], (ckv, k_pe)


def make_mla_cache(cfg, batch: int, max_seq: int, stack: tuple = ()):
    m = cfg.mla
    lead = tuple(stack)
    ll = (None,) * len(lead)
    return {
        "ckv": Param((*lead, batch, max_seq, m.kv_lora_rank),
                     (*ll, "batch", "seq_kv", None), init="zeros",
                     dtype=cfg.dtype),
        "kpe": Param((*lead, batch, max_seq, m.qk_rope_head_dim),
                     (*ll, "batch", "seq_kv", None), init="zeros",
                     dtype=cfg.dtype),
    }


def make_mla_cache_paged(cfg, num_pages: int, page_size: int,
                         stack: tuple = ()):
    """Paged latent cache: (ckv, kpe) pools of ``num_pages × page_size``
    rows shared by every slot through per-slot page tables."""
    m = cfg.mla
    lead = tuple(stack)
    ll = (None,) * len(lead)
    return {
        "ckv": Param((*lead, num_pages, page_size, m.kv_lora_rank),
                     (*ll, None, "seq_kv", None), init="zeros",
                     dtype=cfg.dtype),
        "kpe": Param((*lead, num_pages, page_size, m.qk_rope_head_dim),
                     (*ll, None, "seq_kv", None), init="zeros",
                     dtype=cfg.dtype),
    }


def _absorbed_attention(cfg, p, q_nope, q_pe, ckv, kpe, positions, dtype,
                        layer=None):
    """Weight-absorbed attention of queries at ``positions`` [B, Q] against
    a latent cache ``ckv`` [B, S, r], ``kpe`` [B, S, rope]: W_UK folds into
    the queries, W_UV into the output, so the cache is read as it is
    stored.  With ``layer`` (an int32 scalar) ``ckv`` and ``kpe`` are the
    stacks [L, B, S, *], read at that layer by each einsum that takes
    them, so the slice fuses into the einsum and is never copied out.
    Returns [B, Q, H * v_head_dim] in ``dtype``."""
    B, Q = positions.shape
    m, H = cfg.mla, cfg.num_heads
    read = (lambda c: c) if layer is None else (
        lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False))
    wuk = p["wuk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       wuk.astype(jnp.float32))
    with jax.named_scope(LATENT_SCOPE):
        scores = jnp.einsum("bqhr,bsr->bhqs", q_lat,
                            read(ckv).astype(jnp.float32))
        scores += jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                             read(kpe).astype(jnp.float32))
        scores *= softmax_scale(cfg)
        mask = jnp.arange(ckv.shape[-2])[None, None, :] <= positions[:, :, None]
        scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", probs,
                           read(ckv).astype(jnp.float32))
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wuv.astype(jnp.float32))
    return out.reshape(B, Q, H * m.v_head_dim).astype(dtype)


def apply_mla_prefill_chunk_paged(cfg, p, x, cache, start, page_table,
                                  active=None):
    """Weight-absorbed chunk prefill into the paged latent pools.  Same
    contract as ``apply_mla_prefill_chunk`` with the dense stripe
    replaced by page-table scatter + gather (stale rows sit beyond the
    causal mask)."""
    from repro.kernels.ref import gather_pages

    C = x.shape[1]
    positions = start[:, None] + jnp.arange(C)[None, :]          # [B, C]
    q_nope, q_pe = _queries(cfg, p, x, positions)                # [B,C,H,*]
    ckv_new, kpe_new = _latent_kv(cfg, p, x, positions)
    ckv = paged_write_rows(cache["ckv"], page_table, positions, ckv_new,
                           active)
    kpe = paged_write_rows(cache["kpe"], page_table, positions, kpe_new,
                           active)
    out = _absorbed_attention(cfg, p, q_nope, q_pe,
                              gather_pages(ckv, page_table),
                              gather_pages(kpe, page_table), positions,
                              x.dtype)
    return out @ p["wo"], {"ckv": ckv, "kpe": kpe}


def apply_mla_decode_paged(cfg, p, x, cache, pos, page_table, active=None):
    """Weight-absorbed one-token decode against the paged latent pools.
    x: [B,1,d]; cache {ckv: [P,ps,r], kpe: [P,ps,rope]}; pos: [B];
    page_table: [B,W] int32; active: optional [B] bool."""
    from repro.kernels.ref import gather_pages

    q_nope, q_pe = _queries(cfg, p, x, pos[:, None])  # [B,1,H,*]
    ckv_new, kpe_new = _latent_kv(cfg, p, x, pos[:, None])
    ckv = paged_write_rows(cache["ckv"], page_table, pos, ckv_new[:, 0],
                           active)
    kpe = paged_write_rows(cache["kpe"], page_table, pos, kpe_new[:, 0],
                           active)
    out = _absorbed_attention(cfg, p, q_nope, q_pe,
                              gather_pages(ckv, page_table),
                              gather_pages(kpe, page_table), pos[:, None],
                              x.dtype)
    return out @ p["wo"], {"ckv": ckv, "kpe": kpe}


def apply_mla_prefill_chunk(cfg, p, x, cache, start, active=None,
                            layer=None):
    """Weight-absorbed prefill of a C-token chunk into the latent cache.

    x: [B, C, d]; cache {ckv: [B,S,r], kpe: [B,S,rope]}, or with ``layer``
    (an int32 scalar) the whole stacks {ckv: [L,B,S,r], kpe: [L,B,S,rope]},
    of which layer ``layer`` is written and read in place; start: [B]
    int32 (per-slot cache position of the chunk's first token); active:
    optional [B] bool — inactive slots leave the cache untouched, outputs
    garbage.  Returns (out [B, C, d], new_cache)."""
    C = x.shape[1]
    positions = start[:, None] + jnp.arange(C)[None, :]          # [B, C]
    q_nope, q_pe = _queries(cfg, p, x, positions)                # [B,C,H,*]
    ckv_new, kpe_new = _latent_kv(cfg, p, x, positions)
    ckv = write_rows(cache["ckv"], ckv_new, layer, start, active)
    kpe = write_rows(cache["kpe"], kpe_new, layer, start, active)
    out = _absorbed_attention(cfg, p, q_nope, q_pe, ckv, kpe, positions,
                              x.dtype, layer)
    return out @ p["wo"], {"ckv": ckv, "kpe": kpe}


def apply_mla_decode(cfg, p, x, cache, pos, active=None, layer=None):
    """Weight-absorbed one-token decode.

    x: [B,1,d]; cache {ckv: [B,S,r], kpe: [B,S,rope]}, or with ``layer``
    (an int32 scalar) the whole stacks {ckv: [L,B,S,r], kpe: [L,B,S,rope]},
    of which layer ``layer`` is written and read in place; pos: [B];
    active: optional [B] bool (inactive slots leave the cache untouched)."""
    q_nope, q_pe = _queries(cfg, p, x, pos[:, None])  # [B,1,H,*]
    ckv_new, kpe_new = _latent_kv(cfg, p, x, pos[:, None])
    ckv = write_rows(cache["ckv"], ckv_new, layer, pos, active)
    kpe = write_rows(cache["kpe"], kpe_new, layer, pos, active)
    out = _absorbed_attention(cfg, p, q_nope, q_pe, ckv, kpe, pos[:, None],
                              x.dtype, layer)
    return out @ p["wo"], {"ckv": ckv, "kpe": kpe}
