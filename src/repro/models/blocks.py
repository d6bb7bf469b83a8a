"""Residual blocks: (mixer ∈ {attn, mla, mamba}) + (ffn ∈ {dense, moe, none}),
plus the Jamba super-block (hybrid interleave) and stacking helpers for
jax.lax.scan over layer stacks.

Each half of a block runs under a ``jax.named_scope`` (``MIXER_SCOPE``,
``FFN_SCOPE``), so its operations carry the name in their HLO ``op_name``
metadata and a device profile can attribute time to it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models.layers import (apply_dense_ffn, make_dense_ffn, make_norm,
                                 rmsnorm)
from repro.models.moe import apply_moe, make_moe
from repro.models.params import Param, tree_map

MIXER_SCOPE = {"attn": "attention", "mla": "mla", "mamba": "mamba"}
FFN_SCOPE = {"dense": "mlp", "moe": "moe"}


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------
def make_block(cfg, mixer: str, ffn: str):
    p = {"ln1": make_norm(cfg.d_model)}
    if mixer == "attn":
        p["mixer"] = attn_mod.make_attention(cfg)
    elif mixer == "mla":
        p["mixer"] = mla_mod.make_mla(cfg)
    elif mixer == "mamba":
        p["mixer"] = mamba_mod.make_mamba(cfg)
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        p["ln2"] = make_norm(cfg.d_model)
        p["ffn"] = make_dense_ffn(cfg, cfg.d_ff_dense or cfg.d_ff)
    elif ffn == "moe":
        p["ln2"] = make_norm(cfg.d_model)
        p["ffn"] = make_moe(cfg)
    elif ffn != "none":
        raise ValueError(ffn)
    return p


def _apply_ffn(cfg, p, h, ffn: str):
    """The block's feed-forward half on the residual stream: (h, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if ffn == "none":
        return h, aux
    with jax.named_scope(FFN_SCOPE[ffn]):
        x = rmsnorm(h, p["ln2"], cfg.norm_eps)
        if ffn == "moe":
            B, S, d = x.shape
            y, aux = apply_moe(cfg, p["ffn"], x.reshape(B * S, d))
            y = y.reshape(B, S, d)
        else:
            y = apply_dense_ffn(cfg, p["ffn"], x)
    return h + y, aux


def apply_block(cfg, p, h, positions, mixer: str, ffn: str):
    """Full-sequence residual block. Returns (h, aux_loss)."""
    with jax.named_scope(MIXER_SCOPE[mixer]):
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            r, _ = attn_mod.apply_attention(cfg, p["mixer"], x, positions)
        elif mixer == "mla":
            r, _ = mla_mod.apply_mla(cfg, p["mixer"], x, positions)
        else:
            r, _ = mamba_mod.apply_mamba(cfg, p["mixer"], x, positions)
    return _apply_ffn(cfg, p, h + r, ffn)


def apply_block_collect(cfg, p, h, positions, mixer: str, ffn: str):
    """Like apply_block but also returns the prefill cache
    (attn: {k,v}, mla: {ckv,kpe}, mamba: {conv,ssm})."""
    with jax.named_scope(MIXER_SCOPE[mixer]):
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            r, (k, v) = attn_mod.apply_attention(cfg, p["mixer"], x, positions)
            cache = {"k": k, "v": v}
        elif mixer == "mla":
            r, (ckv, kpe) = mla_mod.apply_mla(cfg, p["mixer"], x, positions)
            cache = {"ckv": ckv, "kpe": kpe}
        else:
            r, (conv, ssm) = mamba_mod.apply_mamba(cfg, p["mixer"], x,
                                                   positions)
            cache = {"conv": conv, "ssm": ssm}
    h, aux = _apply_ffn(cfg, p, h + r, ffn)
    return h, aux, cache


def make_block_cache(cfg, mixer: str, batch: int, max_seq: int,
                     stack: tuple = ()):
    if mixer == "attn":
        return attn_mod.make_kv_cache(cfg, batch, max_seq, stack)
    if mixer == "mla":
        return mla_mod.make_mla_cache(cfg, batch, max_seq, stack)
    return mamba_mod.make_mamba_cache(cfg, batch, stack)


def make_block_cache_paged(cfg, mixer: str, batch: int, num_pages: int,
                           page_size: int, stack: tuple = ()):
    """Paged-layout block cache: attention/MLA KV rides the shared page
    pool; mamba/SSM slots keep their O(1) dense per-slot state (it has no
    sequence axis to page)."""
    if mixer == "attn":
        return attn_mod.make_kv_cache_paged(cfg, num_pages, page_size, stack)
    if mixer == "mla":
        return mla_mod.make_mla_cache_paged(cfg, num_pages, page_size, stack)
    return mamba_mod.make_mamba_cache(cfg, batch, stack)


def apply_block_decode(cfg, p, h, cache, pos, mixer: str, ffn: str,
                       active=None, page_table=None, layer=None):
    """One-token decode. ``page_table`` not None selects the paged cache
    layout for attention/MLA mixers (mamba state is dense either way).
    ``layer`` not None: ``cache`` is the whole layer stack of a dense
    attention segment, written and read at that layer in place.
    Returns (h, new_cache)."""
    with jax.named_scope(MIXER_SCOPE[mixer]):
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            r, new_cache = (attn_mod.apply_attention_decode_paged(
                                cfg, p["mixer"], x, cache, pos, page_table,
                                active)
                            if page_table is not None
                            else attn_mod.apply_attention_decode(
                                cfg, p["mixer"], x, cache, pos, active,
                                layer))
        elif mixer == "mla":
            r, new_cache = (mla_mod.apply_mla_decode_paged(
                                cfg, p["mixer"], x, cache, pos, page_table,
                                active)
                            if page_table is not None
                            else mla_mod.apply_mla_decode(cfg, p["mixer"], x,
                                                          cache, pos, active))
        else:
            r, new_cache = mamba_mod.apply_mamba_decode(
                cfg, p["mixer"], x, cache, pos, active)
    h, _ = _apply_ffn(cfg, p, h + r, ffn)
    return h, new_cache


def apply_block_prefill_chunk(cfg, p, h, cache, start, mixer: str, ffn: str,
                              active=None, page_table=None, layer=None):
    """Chunked prefill through one block. h: [B, C, d]; start: [B] int32
    per-slot cache offset of the chunk; ``page_table`` not None selects
    the paged layout for attention/MLA; ``layer`` as in
    ``apply_block_decode``. Returns (h, new_cache)."""
    with jax.named_scope(MIXER_SCOPE[mixer]):
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            r, new_cache = (attn_mod.apply_attention_prefill_chunk_paged(
                                cfg, p["mixer"], x, cache, start, page_table,
                                active)
                            if page_table is not None
                            else attn_mod.apply_attention_prefill_chunk(
                                cfg, p["mixer"], x, cache, start, active,
                                layer))
        elif mixer == "mla":
            r, new_cache = (mla_mod.apply_mla_prefill_chunk_paged(
                                cfg, p["mixer"], x, cache, start, page_table,
                                active)
                            if page_table is not None
                            else mla_mod.apply_mla_prefill_chunk(
                                cfg, p["mixer"], x, cache, start, active))
        else:
            r, new_cache = mamba_mod.apply_mamba_prefill_chunk(
                cfg, p["mixer"], x, cache, start, active)
    h, _ = _apply_ffn(cfg, p, h + r, ffn)
    return h, new_cache


# ---------------------------------------------------------------------------
# stacking (scan over homogeneous layers)
# ---------------------------------------------------------------------------
def stack_descr(tree, n: int):
    """Prepend a stacked 'layers' dim of size n to every Param descriptor."""
    return tree_map(
        lambda p: Param((n, *p.shape), ("layers", *p.logical), p.init,
                        p.dtype, p.scale),
        tree,
    )


def take_layer(tree, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


# ---------------------------------------------------------------------------
# Jamba super-block
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HybridPlan:
    """Layer plan within one super-block: (group, index_within_group,
    mixer, ffn) per in-block position."""
    entries: tuple  # of (group, idx, mixer, ffn)
    group_sizes: dict

    @staticmethod
    def build(cfg) -> HybridPlan:
        hb = cfg.hybrid_block
        assert hb and cfg.num_layers % hb == 0
        m = cfg.moe
        if m is not None:
            assert hb % m.every == 0, "MoE period must divide the super-block"
        entries, sizes = [], {}
        for i in range(hb):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            ffn = "moe" if (cfg.moe is not None and cfg.is_moe_layer(i)) \
                else "dense"
            group = f"{mixer}_{ffn}"
            idx = sizes.get(group, 0)
            sizes[group] = idx + 1
            entries.append((group, idx, mixer, ffn))
        return HybridPlan(tuple(entries), sizes)


def make_super_block(cfg, plan: HybridPlan):
    p = {}
    for group, n in plan.group_sizes.items():
        mixer, ffn = group.split("_")
        p[group] = stack_descr(make_block(cfg, mixer, ffn), n)
    return p


def apply_super_block(cfg, p, h, positions, plan: HybridPlan):
    aux = jnp.zeros((), jnp.float32)
    for group, idx, mixer, ffn in plan.entries:
        h, a = apply_block(cfg, take_layer(p[group], idx), h, positions,
                           mixer, ffn)
        aux = aux + a
    return h, aux


def apply_super_block_collect(cfg, p, h, positions, plan: HybridPlan):
    aux = jnp.zeros((), jnp.float32)
    per_group = {g: [None] * n for g, n in plan.group_sizes.items()}
    for group, idx, mixer, ffn in plan.entries:
        h, a, cache = apply_block_collect(
            cfg, take_layer(p[group], idx), h, positions, mixer, ffn)
        aux = aux + a
        per_group[group][idx] = cache
    stacked = {
        g: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *lst)
        for g, lst in per_group.items()
    }
    return h, aux, stacked


def make_super_block_cache(cfg, plan: HybridPlan, batch: int, max_seq: int,
                           stack: tuple = ()):
    c = {}
    for group, n in plan.group_sizes.items():
        mixer, _ = group.split("_")
        c[group] = make_block_cache(cfg, mixer, batch, max_seq,
                                    stack=(*stack, n))
    return c


def make_super_block_cache_paged(cfg, plan: HybridPlan, batch: int,
                                 num_pages: int, page_size: int,
                                 stack: tuple = ()):
    c = {}
    for group, n in plan.group_sizes.items():
        mixer, _ = group.split("_")
        c[group] = make_block_cache_paged(cfg, mixer, batch, num_pages,
                                          page_size, stack=(*stack, n))
    return c


def apply_super_block_prefill_chunk(cfg, p, h, cache, start,
                                    plan: HybridPlan, active=None,
                                    page_table=None):
    new_cache = {g: [None] * n for g, n in plan.group_sizes.items()}
    for group, idx, mixer, ffn in plan.entries:
        h, nc = apply_block_prefill_chunk(
            cfg, take_layer(p[group], idx), h, take_layer(cache[group], idx),
            start, mixer, ffn, active, page_table)
        new_cache[group][idx] = nc
    stacked = {}
    for g, lst in new_cache.items():
        stacked[g] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *lst)
    return h, stacked


def apply_super_block_decode(cfg, p, h, cache, pos, plan: HybridPlan,
                             active=None, page_table=None):
    new_cache = {g: [None] * n for g, n in plan.group_sizes.items()}
    for group, idx, mixer, ffn in plan.entries:
        h, nc = apply_block_decode(
            cfg, take_layer(p[group], idx), h, take_layer(cache[group], idx),
            pos, mixer, ffn, active, page_table)
        new_cache[group][idx] = nc
    # restack each group's caches along the leading dim
    stacked = {}
    for g, lst in new_cache.items():
        stacked[g] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *lst)
    return h, stacked
