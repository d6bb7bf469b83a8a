"""LM assembly: embeddings (incl. multi-codebook audio and VLM stub merge),
scanned layer segments, chunked cross-entropy, MTP head, and the three
entry points the launcher lowers:

  * ``train_loss(cfg, params, batch)``            (train_4k)
  * ``prefill(cfg, params, batch)``               (prefill_32k)
  * ``decode_step(cfg, params, batch, cache)``    (decode_32k / long_500k)
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.layers import make_norm, rmsnorm, make_embedding
from repro.models.params import Param, init_params, abstract_params
from repro.sharding.rules import shard


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Segment:
    kind: str              # 'blocks' | 'hybrid'
    count: int
    mixer: str = "attn"
    ffn: str = "dense"
    plan: object = None


def segments(cfg) -> list[Segment]:
    if cfg.hybrid_block:
        plan = B.HybridPlan.build(cfg)
        return [Segment("hybrid", cfg.num_layers // cfg.hybrid_block,
                        plan=plan)]
    if cfg.family == "ssm":
        return [Segment("blocks", cfg.num_layers, mixer="mamba", ffn="none")]
    mixer = "mla" if cfg.attention_kind == "mla" else "attn"
    if cfg.moe is None:
        return [Segment("blocks", cfg.num_layers, mixer=mixer, ffn="dense")]
    segs = []
    fk = cfg.moe.first_k_dense
    if fk:
        segs.append(Segment("blocks", fk, mixer=mixer, ffn="dense"))
    assert cfg.moe.every == 1, "periodic MoE outside hybrid_block unsupported"
    segs.append(Segment("blocks", cfg.num_layers - fk, mixer=mixer, ffn="moe"))
    return segs


# ---------------------------------------------------------------------------
# descriptors
# ---------------------------------------------------------------------------
def make_lm(cfg):
    d = cfg.d_model
    p: dict = {}
    p["embed"] = (Param((cfg.num_codebooks, cfg.vocab_size, d),
                        ("codebooks", "vocab", "embed"), init="normal",
                        scale=0.02)
                  if cfg.num_codebooks
                  else make_embedding(cfg.vocab_size, d))
    segs = []
    for seg in segments(cfg):
        if seg.kind == "hybrid":
            segs.append(B.stack_descr(B.make_super_block(cfg, seg.plan),
                                      seg.count))
        else:
            segs.append(B.stack_descr(B.make_block(cfg, seg.mixer, seg.ffn),
                                      seg.count))
    p["segments"] = segs
    p["final_norm"] = make_norm(d)
    if not cfg.tie_embeddings:
        p["lm_head"] = (Param((cfg.num_codebooks, d, cfg.vocab_size),
                              ("codebooks", "embed", "vocab"), init="scaled")
                        if cfg.num_codebooks
                        else Param((d, cfg.vocab_size), ("embed", "vocab"),
                                   init="scaled"))
    if cfg.mtp_depth:
        p["mtp"] = [
            {
                "norm_h": make_norm(d),
                "norm_e": make_norm(d),
                "proj": Param((2 * d, d), (None, "embed"), init="scaled"),
                "block": B.make_block(
                    cfg, "mla" if cfg.attention_kind == "mla" else "attn",
                    "dense"),
            }
            for _ in range(cfg.mtp_depth)
        ]
    return p


def init_lm(cfg, rng):
    return init_params(make_lm(cfg), rng)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------
def embed_tokens(cfg, params, tokens, batch=None):
    if cfg.num_codebooks:
        # tokens [B, S, cb]; embed [cb, V, d]
        tcb = jnp.moveaxis(tokens, -1, 0)  # [cb, B, S]
        h = jax.vmap(lambda tab, t: jnp.take(tab, t, axis=0))(
            params["embed"], tcb).sum(axis=0)
    else:
        h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.vision_stub and batch is not None and "image_embeds" in batch:
        img = batch["image_embeds"].astype(h.dtype)   # [B, N, d]
        pos = batch["image_positions"]                 # [B, N] int32
        b_idx = jnp.arange(h.shape[0])[:, None]
        h = h.at[b_idx, pos].set(img)
    return shard(h, "batch", "seq", "embed")


def head_weights(cfg, params):
    if cfg.tie_embeddings:
        return jnp.swapaxes(params["embed"], -1, -2)  # [d, V] (or [cb, d, V])
    return params["lm_head"]


@jax.named_scope("head")
def apply_head(cfg, params, h):
    w = head_weights(cfg, params)
    if cfg.num_codebooks:
        return jnp.einsum("...d,cdv->...cv", h, w)
    return h @ w


# ---------------------------------------------------------------------------
# backbone
# ---------------------------------------------------------------------------
def _segment_scan(cfg, seg: Segment, seg_params, h, positions, *,
                  remat: bool, collect: bool, unroll: bool = False):
    def body(carry, layer_p):
        hh = carry
        if seg.kind == "hybrid":
            if collect:
                hh, aux, cache = B.apply_super_block_collect(
                    cfg, layer_p, hh, positions, seg.plan)
                return hh, (aux, cache)
            hh, aux = B.apply_super_block(cfg, layer_p, hh, positions,
                                          seg.plan)
            return hh, (aux, None)
        if collect:
            hh, aux, cache = B.apply_block_collect(cfg, layer_p, hh,
                                                   positions, seg.mixer,
                                                   seg.ffn)
            return hh, (aux, cache)
        hh, aux = B.apply_block(cfg, layer_p, hh, positions, seg.mixer,
                                seg.ffn)
        return hh, (aux, None)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    h, (auxs, caches) = jax.lax.scan(
        body, h, seg_params, unroll=seg.count if unroll else 1)
    return h, jnp.sum(auxs), caches


def backbone(cfg, params, h, positions, *, remat: bool = True,
             collect: bool = False, unroll: bool = False):
    """Returns (h, aux_loss, caches-per-segment or None)."""
    aux = jnp.zeros((), jnp.float32)
    caches = []
    for seg, seg_params in zip(segments(cfg), params["segments"],
                               strict=False):
        h, a, c = _segment_scan(cfg, seg, seg_params, h, positions,
                                remat=remat, collect=collect, unroll=unroll)
        aux = aux + a
        caches.append(c)
    return h, aux, (caches if collect else None)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def _xent_chunk(cfg, params, h, targets, mask):
    """Cross-entropy for one [B, C, d] chunk, fp32. Returns (sum_loss, n)."""
    logits = apply_head(cfg, params, h).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if cfg.num_codebooks:
        nll = jnp.mean(nll, axis=-1)  # average over codebooks
    mf = mask.astype(jnp.float32)
    return jnp.sum(nll * mf), jnp.sum(mf)


@jax.named_scope("loss")
def chunked_xent(cfg, params, h, targets, mask, chunk: int = 512):
    """Sequence-chunked xent: avoids materialising [B, S, V] logits."""
    import os as _os2

    chunk = int(_os2.environ.get("REPRO_XENT_CHUNK", chunk))
    Bsz, S = h.shape[0], h.shape[1]
    if S <= chunk:
        s, n = _xent_chunk(cfg, params, h, targets, mask)
        return s / jnp.maximum(n, 1.0)
    n_chunks = S // chunk
    rem = S - n_chunks * chunk

    def one(carry, i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, 1)
        s_, n_ = _xent_chunk(cfg, params, sl(h), sl(targets), sl(mask))
        return carry, (s_, n_)

    import os as _os
    _unr = n_chunks if _os.environ.get("REPRO_UNROLL_INNER") else 1
    _, (sums, counts) = jax.lax.scan(one, 0, jnp.arange(n_chunks),
                                     unroll=_unr)
    total, n = jnp.sum(sums), jnp.sum(counts)
    if rem:
        s2, n2 = _xent_chunk(cfg, params, h[:, -rem:], targets[:, -rem:],
                             mask[:, -rem:])
        total, n = total + s2, n + n2
    return total / jnp.maximum(n, 1.0)


def train_loss(cfg, params, batch, *, remat: bool = True,
               unroll: bool = False):
    """batch: tokens [B,S] (or [B,S,cb]); optional loss_mask [B,S],
    image_embeds/image_positions (vlm). Returns (loss, metrics)."""
    tokens = batch["tokens"]
    Bsz, S = tokens.shape[0], tokens.shape[1]
    positions = jnp.arange(S)[None, :]
    h = embed_tokens(cfg, params, tokens, batch)
    h, aux, _ = backbone(cfg, params, h, positions, remat=remat,
                         unroll=unroll)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones((Bsz, S), jnp.float32)
    tgt_tok = tokens[:, 1:]
    ce = chunked_xent(cfg, params, h[:, :-1],
                      tgt_tok if cfg.num_codebooks else tgt_tok,
                      mask[:, 1:])
    loss = ce + aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp_depth:
        mtp_loss = jnp.zeros((), jnp.float32)
        h_prev = h
        for depth, mp in enumerate(params["mtp"], start=1):
            emb = embed_tokens(cfg, params, tokens, batch)
            hm_in = jnp.concatenate(
                [rmsnorm(h_prev[:, :-1], mp["norm_h"], cfg.norm_eps),
                 rmsnorm(emb[:, 1:], mp["norm_e"], cfg.norm_eps)],
                axis=-1) @ mp["proj"]
            hm, _ = B.apply_block(
                cfg, mp["block"], hm_in, positions[:, 1:],
                "mla" if cfg.attention_kind == "mla" else "attn", "dense")
            # predict token t+1+depth from position t
            d1 = depth + 1
            mtp_loss = mtp_loss + chunked_xent(
                cfg, params, hm[:, : S - d1], tokens[:, d1:],
                mask[:, d1:])
            h_prev = jnp.pad(hm, ((0, 0), (0, 1), (0, 0)))
        loss = loss + cfg.mtp_loss_weight * mtp_loss / cfg.mtp_depth
        metrics["mtp"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------
def prefill(cfg, params, batch, *, unroll: bool = False):
    """Full-sequence forward returning (last-token logits, caches)."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    positions = jnp.arange(S)[None, :]
    h = embed_tokens(cfg, params, tokens, batch)
    h, _, caches = backbone(cfg, params, h, positions, remat=False,
                            collect=True, unroll=unroll)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = apply_head(cfg, params, h[:, -1])
    return logits, caches


def make_cache(cfg, batch_size: int, max_seq: int,
               paged: tuple[int, int] | None = None):
    """Descriptor tree for the decode cache (one entry per segment).

    ``paged=(num_pages, page_size)`` selects the paged layout: KV leaves
    become shared ``[num_pages, page_size, ...]`` pools addressed through
    per-slot page tables (``batch["page_table"]`` at apply time) instead
    of dense ``[batch, max_seq, ...]`` stripes; SSM/conv state keeps its
    dense O(1) per-slot layout in both."""
    out = []
    for seg in segments(cfg):
        if seg.kind == "hybrid":
            out.append(B.make_super_block_cache_paged(
                           cfg, seg.plan, batch_size, *paged,
                           stack=(seg.count,))
                       if paged is not None
                       else B.make_super_block_cache(
                           cfg, seg.plan, batch_size, max_seq,
                           stack=(seg.count,)))
        else:
            out.append(B.make_block_cache_paged(
                           cfg, seg.mixer, batch_size, *paged,
                           stack=(seg.count,))
                       if paged is not None
                       else B.make_block_cache(
                           cfg, seg.mixer, batch_size, max_seq,
                           stack=(seg.count,)))
    return out


def _cache_scan(cfg, params, h, cache, page_table, apply, unroll):
    """Run ``h`` through every layer, each segment's stacked cache riding
    the layer scan's carry, so XLA updates it in place at every loop level
    (the step programs donate it).

    ``apply(seg, layer_p, h, c, layer)`` runs one layer and returns
    ``(h, c)``.  An attention or MLA segment with no page table hands it
    the whole stack and the layer index: the mixer writes its new rows and
    reads the layer in place.  Other segments (mamba, the hybrid
    super-block, a paged pool) get ``layer`` None and the layer's own
    leaves, read out of the carry and written back after.
    Returns (h, new_cache)."""
    new_caches = []
    for seg, seg_params, seg_cache in zip(segments(cfg), params["segments"],
                                          cache, strict=False):
        whole = (seg.kind == "blocks" and seg.mixer in ("attn", "mla")
                 and page_table is None)

        def body(carry, xs, seg=seg, whole=whole):
            hh, c = carry
            layer_p, layer = xs
            if whole:
                return apply(seg, layer_p, hh, c, layer), None
            at = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, layer, 0, False), c)
            hh, at = apply(seg, layer_p, hh, at, None)
            c = jax.tree_util.tree_map(
                lambda x, y: jax.lax.dynamic_update_index_in_dim(
                    x, y, layer, 0), c, at)
            return (hh, c), None

        (h, new_c), _ = jax.lax.scan(
            body, (h, seg_cache), (seg_params, jnp.arange(seg.count)),
            unroll=seg.count if unroll else 1)
        new_caches.append(new_c)
    return h, new_caches


def prefill_chunk(cfg, params, batch, cache, *, unroll: bool = False):
    """Prefill a C-token chunk into slot caches (continuous batching).

    batch: tokens [B, C(,cb)], start [B] int32 (per-slot cache offset of
    the chunk's first token), optional active [B] bool (inactive slots'
    caches pass through untouched).  No head/logits — admission runs this
    to warm the cache; the first sampled token always comes from the
    decode path.  Optional ``page_table`` [B, W] int32 selects the paged
    cache layout.  Returns new_cache only."""
    tokens, start = batch["tokens"], batch["start"]
    active = batch.get("active")
    page_table = batch.get("page_table")

    def apply(seg, p, hh, c, layer):
        if seg.kind == "hybrid":
            return B.apply_super_block_prefill_chunk(
                cfg, p, hh, c, start, seg.plan, active, page_table)
        return B.apply_block_prefill_chunk(cfg, p, hh, c, start, seg.mixer,
                                           seg.ffn, active, page_table,
                                           layer)

    h = embed_tokens(cfg, params, tokens, batch)
    _, new_caches = _cache_scan(cfg, params, h, cache, page_table, apply,
                                unroll)
    return new_caches


def decode_step(cfg, params, batch, cache, *, unroll: bool = False):
    """One decode step. batch: tokens [B,1(,cb)], pos [B] int32, optional
    page_table [B, W] int32 (paged cache layout).
    Returns (logits [B, V(,cb)], new_cache)."""
    tokens, pos = batch["tokens"], batch["pos"]
    active = batch.get("active")
    page_table = batch.get("page_table")

    def apply(seg, p, hh, c, layer):
        if seg.kind == "hybrid":
            return B.apply_super_block_decode(cfg, p, hh, c, pos, seg.plan,
                                              active, page_table)
        return B.apply_block_decode(cfg, p, hh, c, pos, seg.mixer, seg.ffn,
                                    active, page_table, layer)

    h = embed_tokens(cfg, params, tokens, batch)
    h, new_caches = _cache_scan(cfg, params, h, cache, page_table, apply,
                                unroll)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = apply_head(cfg, params, h[:, -1])
    return logits, new_caches
