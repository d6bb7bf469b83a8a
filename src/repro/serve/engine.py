"""Batched decode engine with slot-based continuous batching.

Requests are admitted into fixed batch slots between decode steps.  Each
slot carries its own position counter (positions are a [B] vector through
the model) and an ``active`` mask: inactive slots write nothing to the KV
cache and keep their SSM/conv state frozen, so admission/retirement of one
request never perturbs the others — this is what makes continuous batching
correct for hybrid/SSM architectures, not just KV-cache transformers.

Two stepping modes:

* ``mode="fused"`` (default): sampling runs *inside* the jitted step —
  per-slot PRNG keys split on device, temperature/top-k as traced [B]
  vectors, prompt forcing / emission / retirement bookkeeping as device
  arrays — and a ``lax.scan`` runs ``steps_per_sync`` decode steps per
  host round-trip.  The host only syncs to unpack emitted tokens and
  admit/retire requests.
* ``mode="host"``: the per-step-host-sync baseline (one decode dispatch,
  full-logits device->host transfer, per-slot python sampling per step) —
  the seed engine's cost profile with its correctness bugs fixed
  (per-slot RNG keys instead of one shared subkey, deque admission,
  single-trace sampling via a traced temperature).  Kept as the
  benchmark baseline; greedy outputs are identical across modes.

Two KV-cache layouts:

* ``kv_layout="dense"``: every slot owns a ``max_seq`` KV stripe — HBM
  scales with ``slots × max_seq`` even for short requests.
* ``kv_layout="paged"``: KV rides a shared pool of ``num_pages ×
  page_size`` rows (``serve/kv_pool.py``) addressed through per-slot
  page tables.  Admission is memory-aware (a request is admitted only
  when its prompt's page footprint fits), pages are allocated lazily as
  a slot's position crosses page boundaries (once per sync, covering the
  sync's worst-case advance), and retirement frees them O(1).  On pool
  exhaustion the *youngest* slot is preempted and its request requeued
  at-least-once — the oldest slot can always run to completion (the
  constructor requires ``num_pages >= ceil(max_seq/page_size)``), so the
  engine never deadlocks and every submitted request still completes.
  Greedy outputs are identical to the dense layout; a preempted
  temperature>0 request restarts on a fresh RNG stream.

Prompt consumption is sequential forced decode by default; with
``prefill_chunk=C > 0`` admission runs batched C-token prefill chunks
into the slot's cache (``lm.prefill_chunk``) and only the remainder of
the prompt goes through forced decode, with
``max_prefill_tokens_per_sync`` bounding per-sync prefill work so decode
latency of resident slots stays flat.

Malformed prompts (empty, or too long for ``max_seq``) are rejected with
a typed failure (``Request.failed`` + ``fail_reason``) instead of
crashing the engine; serving continues for everyone else.

Under a running ``jax.profiler`` trace, ``step()`` records host spans
named ``repro.serve.*``: ``step`` around the call, and inside it ``admit``,
``prefill`` (one chunk call's host preparation and dispatch), and in fused
mode ``decode_dispatch`` (state upload and the launch), ``decode_wait``
(the blocking device-to-host transfers) and ``unpack`` (emissions and
retirement).  ``prefill`` and ``unpack`` carry the per-call increments of
the row counters in ``stats``.  With the profiler off a span costs one
check; it adds no device work and no transfer.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.params import init_params, is_param
from repro.serve.kv_pool import KVPool, PoolExhausted
from repro.serve.sampler import sample, sample_batch


# paged-KV geometry served on a tune-cache miss (the pre-tuning default)
_DEFAULT_PAGE_SIZE = 16

_span = jax.profiler.TraceAnnotation


def _resolve_page_size(cfg, batch_slots: int, max_seq: int) -> int:
    """Tuned ``page_size`` for this engine's decode geometry.

    Consults the ``repro.tune`` best-config cache under the
    ``decode_attention_paged`` key (shape = this engine's steady-state
    decode call: B=slots, Sk=max_seq, GQA geometry from cfg).  A miss —
    or a cfg without GQA attention fields (pure-SSM / MLA stacks, whose
    paged pool is not the tuned kernel) — returns the built-in default,
    keeping behavior byte-identical when no cache is present.  A tuned
    value is re-validated against the kernel's constraint
    (0 < page_size <= max_seq) so a stale entry degrades to the default."""
    kvh = getattr(cfg, "num_kv_heads", None)
    heads = getattr(cfg, "num_heads", None)
    hd = getattr(cfg, "head_dim", None)
    if not (kvh and heads and hd):
        return _DEFAULT_PAGE_SIZE
    from repro.tune import cache as tune_cache

    shape = {"b": batch_slots, "sk": max_seq, "kvh": kvh,
             "g": max(1, heads // kvh), "d": hd}
    hit = tune_cache.best_config("decode_attention_paged", shape,
                                 str(getattr(cfg, "dtype", "float32")))
    ps = int((hit or {}).get("page_size", _DEFAULT_PAGE_SIZE))
    if not 0 < ps <= max_seq:
        ps = _DEFAULT_PAGE_SIZE
    return ps


@dataclass
class Request:
    prompt: np.ndarray          # [S] (or [S, cb]) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    output: list = field(default_factory=list)
    done: bool = False
    failed: bool = False        # typed rejection (bad prompt) — never served
    fail_reason: str | None = None


# ---------------------------------------------------------------------------
# module-level jits (static cfg is hashable -> engines share compilations)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _decode_once(cfg, params, cache, tokens, pos, active, page_table):
    batch = {"tokens": tokens, "pos": pos, "active": active}
    if page_table is not None:
        batch["page_table"] = page_table
    return lm.decode_step(cfg, params, batch, cache)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _prefill_chunk(cfg, params, cache, tokens, start, active, page_table):
    batch = {"tokens": tokens, "start": start, "active": active}
    if page_table is not None:
        batch["page_table"] = page_table
    return lm.prefill_chunk(cfg, params, batch, cache)


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _zero_leaves(leaves, mask, axes):
    """Zero the slots selected by ``mask`` along each leaf's batch axis
    (axis None = leave the leaf untouched).  Module-level so the
    compilation is shared across engine instances."""
    out = []
    for leaf, ax in zip(leaves, axes, strict=True):
        if ax is None:
            out.append(leaf)
        else:
            shape = [1] * leaf.ndim
            shape[ax] = leaf.shape[ax]
            out.append(jnp.where(mask.reshape(shape),
                                 jnp.zeros_like(leaf), leaf))
    return out


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _zero_page_leaves(pool_leaves, page_ids, page_axes):
    """Zero the given physical pages of each pool leaf (page axis per
    leaf in ``page_axes``).  Out-of-range ids (the pad sentinel) drop."""
    out = []
    for leaf, pax in zip(pool_leaves, page_axes, strict=True):
        idx = (slice(None),) * pax + (page_ids,)
        zeros = jnp.zeros((*leaf.shape[:pax], page_ids.shape[0],
                           *leaf.shape[pax + 1:]), leaf.dtype)
        out.append(leaf.at[idx].set(zeros, mode="drop"))
    return out


def _gather_pool_views(leaves, pool_idx, page_axes, page_table):
    """Replace pool leaves with sync-local dense [.., B, W*ps, ..] views."""
    B, W = page_table.shape
    out = list(leaves)
    for i, pax in zip(pool_idx, page_axes, strict=True):
        leaf = leaves[i]                        # [*lead, P, ps, *tail]
        P, ps = leaf.shape[pax], leaf.shape[pax + 1]
        ptc = jnp.minimum(page_table, P - 1)    # clamp unmapped sentinels
        g = jnp.take(leaf, ptc, axis=pax)       # [*lead, B, W, ps, *tail]
        out[i] = g.reshape(*leaf.shape[:pax], B, W * ps,
                           *leaf.shape[pax + 2:])
    return out


def _scatter_rows_back(pool_leaf, view_leaf, pax, page_table, positions,
                       keep):
    """Write rows ``positions`` of the dense view back into the pool.

    positions: [B, n] logical rows the sync may have written; keep: [B, n]
    bool — dropped rows (dead slots, rows past max_seq) scatter to an
    out-of-range sentinel.  Rows a slot stopped writing mid-sync carry
    their own gathered content, so writing them back is a no-op."""
    P, ps = pool_leaf.shape[pax], pool_leaf.shape[pax + 1]
    W = page_table.shape[1]
    B, n = positions.shape
    smax = view_leaf.shape[pax + 1]
    idx = positions.reshape((1,) * pax + (B, n)
                            + (1,) * (view_leaf.ndim - pax - 2))
    vals = jnp.take_along_axis(view_leaf, jnp.clip(idx, 0, smax - 1),
                               axis=pax + 1)   # [*lead, B, n, *tail]
    pg = jnp.clip(positions // ps, 0, W - 1)
    phys = jnp.take_along_axis(page_table, pg, axis=1)          # [B, n]
    flat = jnp.where(keep, phys * ps + positions % ps, P * ps)
    rows = pool_leaf.reshape(*pool_leaf.shape[:pax], P * ps,
                             *pool_leaf.shape[pax + 2:])
    rows = rows.at[(slice(None),) * pax + (flat.reshape(-1),)].set(
        vals.reshape(*vals.shape[:pax], B * n, *vals.shape[pax + 2:]),
        mode="drop")
    return rows.reshape(pool_leaf.shape)


@partial(jax.jit, static_argnums=(0, 1, 9), donate_argnums=(3, 4))
def _fused_steps(cfg, n_steps, params, cache, state, prompt_buf, temp, topk,
                 page_table, paged_meta):
    """Run ``n_steps`` decode steps fully on device.

    state: {tokens [B,1(,cb)], pos/cursor/plen/remaining [B] i32,
    live [B] bool, keys [B,2] u32}.  page_table: [B, W] int32 or None —
    constant across the sync (the host allocator pre-extends tables to
    cover the sync's worst-case position advance).  Because the table is
    frozen, the paged layout hoists page indirection out of the step
    loop: gather each KV pool to a sync-local dense view once, run the
    *dense* decode body over it, and scatter the <= n_steps freshly
    written rows per slot back into the pool at the end — per-step cost
    is identical to the dense layout.  (The per-step paged kernel path
    stays live through ``mode="host"`` and chunked prefill.)
    paged_meta: static (pool leaf indices, page axes) locating the pool
    leaves in the flattened cache.  Returns (cache, state,
    sampled [n,B(,cb)], emit [n,B]) — the host unpacks emissions in step
    order after the single sync."""
    max_seq = prompt_buf.shape[1]
    b_idx = jnp.arange(prompt_buf.shape[0])
    pos0, live0 = state["pos"], state["live"]
    if page_table is not None:
        pool_idx, page_axes = paged_meta
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        pools = [leaves[i] for i in pool_idx]
        cache = jax.tree_util.tree_unflatten(
            treedef,
            _gather_pool_views(leaves, pool_idx, page_axes, page_table))

    def body(carry, _):
        cache, st = carry
        tokens, live, pos = st["tokens"], st["live"], st["pos"]
        cursor, plen, remaining = st["cursor"], st["plen"], st["remaining"]
        batch = {"tokens": tokens, "pos": pos, "active": live}
        logits, cache = lm.decode_step(cfg, params, batch, cache)
        pos = pos + live
        ks = jax.vmap(lambda k: jax.random.split(k, 2))(st["keys"])
        keys, subs = ks[:, 0], ks[:, 1]
        # every slot advances its stream every step (dead-slot draws are
        # discarded) so a request's stream doesn't depend on neighbours
        sampled = sample_batch(logits, subs, temp, topk)     # [B(,cb)]
        forcing = cursor < plen
        forced = prompt_buf[b_idx, jnp.clip(cursor, 0, max_seq - 1)]
        sel = forcing if sampled.ndim == 1 else forcing[:, None]
        lv = live if sampled.ndim == 1 else live[:, None]
        nxt = jnp.where(lv, jnp.where(sel, forced, sampled), tokens[:, 0])
        cursor = cursor + (forcing & live)
        emit = live & ~forcing
        remaining = remaining - emit
        done_now = emit & ((remaining <= 0) | (pos >= max_seq - 1))
        st = {"tokens": nxt[:, None], "pos": pos, "cursor": cursor,
              "plen": plen, "remaining": remaining,
              "live": live & ~done_now, "keys": keys}
        return (cache, st), (sampled, emit)

    (cache, state), (sampled, emit) = jax.lax.scan(
        body, (cache, state), None, length=n_steps)
    if page_table is not None:
        positions = pos0[:, None] + jnp.arange(n_steps)[None, :]
        keep = live0[:, None] & (positions < max_seq)
        new_leaves, _ = jax.tree_util.tree_flatten(cache)
        out = list(new_leaves)
        for i, pax, pool in zip(pool_idx, page_axes, pools, strict=True):
            out[i] = _scatter_rows_back(pool, new_leaves[i], pax,
                                        page_table, positions, keep)
        cache = jax.tree_util.tree_unflatten(treedef, out)
    return cache, state, sampled, emit


class DecodeEngine:
    def __init__(self, cfg, params, *, batch_slots: int = 4,
                 max_seq: int = 512, rng_seed: int = 0, mode: str = "fused",
                 steps_per_sync: int = 8, prefill_chunk: int = 0,
                 max_prefill_tokens_per_sync: int | None = None,
                 kv_layout: str = "dense", page_size: int | None = None,
                 num_pages: int | None = None):
        assert mode in ("fused", "host"), mode
        assert kv_layout in ("dense", "paged"), kv_layout
        self.cfg = cfg
        self.params = params
        self.B = batch_slots
        self.max_seq = max_seq
        self.mode = mode
        self.steps_per_sync = max(1, int(steps_per_sync))
        self.prefill_chunk = int(prefill_chunk)
        self.max_prefill_tokens_per_sync = max_prefill_tokens_per_sync
        self.kv_layout = kv_layout

        if kv_layout == "paged":
            # explicit page_size > tuned cache > default (16)
            if page_size is None:
                page_size = _resolve_page_size(cfg, batch_slots, max_seq)
            width = -(-max_seq // int(page_size))
            if num_pages is None:
                # capacity parity with the dense layout by default; size
                # the pool below slots*width for memory-aware admission
                num_pages = batch_slots * width
            assert num_pages >= width, (
                f"num_pages={num_pages} cannot back one full sequence "
                f"(need >= ceil(max_seq/page_size) = {width}); the oldest "
                "slot could deadlock")
            self.pool: KVPool | None = KVPool(num_pages, int(page_size),
                                             batch_slots, max_seq)
            self._paged_arg = (int(num_pages), int(page_size))
        else:
            self.pool = None
            self._paged_arg = None
        cache_descr = lm.make_cache(cfg, batch_slots, max_seq,
                                    paged=self._paged_arg)
        self.cache = init_params(cache_descr, jax.random.PRNGKey(0))

        B = batch_slots
        cb_tail = (cfg.num_codebooks,) if cfg.num_codebooks else ()
        self.tokens = np.zeros((B, 1, *cb_tail), np.int32)
        self.pos = np.zeros((B,), np.int32)
        self.cursor = np.zeros((B,), np.int32)
        self.plen = np.zeros((B,), np.int32)
        self.remaining = np.zeros((B,), np.int32)
        self.live = np.zeros((B,), bool)
        self.keys = np.zeros((B, 2), np.uint32)
        self.temp = np.zeros((B,), np.float32)
        self.topk = np.zeros((B,), np.int32)
        self.prompt_buf = np.zeros((B, max_seq, *cb_tail), np.int32)
        self.pf_target = np.zeros((B,), np.int32)   # tokens to chunk-prefill
        self.pf_done = np.zeros((B,), np.int32)
        self.slot_admit = np.full((B,), -1, np.int64)  # admission order
        self.slot_req: list[Request | None] = [None] * B
        self.queue: collections.deque[Request] = collections.deque()
        self.steps = 0
        self._root_key = jax.random.PRNGKey(rng_seed)
        self._admitted = 0
        # row counters: prefill rows computed (B x C a chunk call) and of
        # those the admitted slots' rows; decode rows computed (B a step),
        # live (a slot decoding), forced (the sampled token replaced by the
        # next prompt token) and emitted (forced + emitted = live)
        self.stats = {"admissions": 0, "rejected": 0, "preemptions": 0,
                      "admit_cache_elems": 0, "peak_occupied": 0,
                      "prefill_rows": 0, "prefill_rows_active": 0,
                      "decode_rows": 0, "decode_rows_live": 0,
                      "decode_rows_forced": 0, "decode_rows_emitted": 0}

        # slot-state leaves (SSM/conv — anything without a seq_kv axis)
        # must be zeroed when a slot is reused: position masking protects
        # KV rows, but recurrent state would leak the previous occupant.
        descr = jax.tree_util.tree_leaves(cache_descr, is_leaf=is_param)
        self._state_axes = tuple(
            None if "seq_kv" in p.logical else p.logical.index("batch")
            for p in descr)
        self._state_idx = tuple(i for i, ax in enumerate(self._state_axes)
                                if ax is not None)
        self._has_state = bool(self._state_idx)
        self._cache_elems = sum(int(np.prod(p.shape)) for p in descr)
        self._state_elems = sum(int(np.prod(descr[i].shape))
                                for i in self._state_idx)

        if kv_layout == "paged":
            # paged admission touches *only* the O(1) per-slot state
            # leaves (KV pool pages are re-zeroed on allocation instead,
            # so admission cost is independent of max_seq); dense keeps
            # the seed behaviour — the admission jit round-trips every
            # cache leaf, KV stripes included.
            # pool leaves: page axis sits just before the page_seq axis
            self._pool_idx = tuple(i for i, ax in enumerate(self._state_axes)
                                   if ax is None)
            self._pool_page_ax = tuple(
                descr[i].logical.index("seq_kv") - 1 for i in self._pool_idx)
            self._page_elems = sum(
                int(np.prod(descr[i].shape)) // descr[i].shape[
                    descr[i].logical.index("seq_kv") - 1]
                for i in self._pool_idx)   # elems zeroed per page
            self._paged_meta = (self._pool_idx, self._pool_page_ax)
            self._pt_dev = jnp.asarray(self.pool.table)
            self._pt_stale = False
        else:
            self._paged_meta = None

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def kv_stats(self) -> dict:
        """Accounting surface: engine counters + pool occupancy."""
        out = dict(self.stats)
        out["kv_layout"] = self.kv_layout
        out["cache_elems"] = self._cache_elems
        if self.pool is not None:
            out.update(self.pool.stats())
            out["slot_footprint"] = [self.pool.footprint(s)
                                     for s in range(self.B)]
        return out

    # -- paged-pool plumbing -------------------------------------------
    def _sync_page_table(self):
        if self._pt_stale:
            self._pt_dev = jnp.asarray(self.pool.table)
            self._pt_stale = False

    def _flush_dirty_pages(self, dirty: list[int]):
        """Zero freshly allocated pages (they may carry a previous
        occupant's rows).  Cost is proportional to pages allocated —
        never to max_seq.  Padded to a power of two so the jit traces
        O(log pool) distinct shapes; the pad sentinel is out of range
        and dropped."""
        if not dirty:
            return
        n = 1
        while n < len(dirty):
            n *= 2
        ids = np.full((n,), self.pool.num_pages, np.int32)
        ids[:len(dirty)] = dirty
        leaves, treedef = jax.tree_util.tree_flatten(self.cache)
        pool_leaves = [leaves[i] for i in self._pool_idx]
        new_pool = _zero_page_leaves(pool_leaves, jnp.asarray(ids),
                                     self._pool_page_ax)
        for i, leaf in zip(self._pool_idx, new_pool, strict=True):
            leaves[i] = leaf
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)
        self.stats["admit_cache_elems"] += len(dirty) * self._page_elems

    def _preempt(self, slot: int):
        """Evict ``slot`` on pool exhaustion: free its pages O(1) and
        requeue its request at-least-once (output restarts from the
        prompt on readmission; a temperature>0 request resamples on a
        fresh RNG stream)."""
        req = self.slot_req[slot]
        self.pool.free_slot(slot)
        self._pt_stale = True
        self.slot_req[slot] = None
        self.live[slot] = False
        self.pf_target[slot] = 0
        self.pf_done[slot] = 0
        self.slot_admit[slot] = -1
        req.output.clear()
        req.done = False
        self.queue.appendleft(req)
        self.stats["preemptions"] += 1

    def _reclaim_for(self, slot: int, upto_pos: int) -> list[int] | None:
        """Extend ``slot``'s page table to back ``upto_pos``, preempting
        *younger* occupied slots while the free list is short.  Returns
        the fresh page ids, or None if ``slot`` itself had to be
        preempted (it was the youngest).  The oldest occupied slot always
        succeeds (num_pages >= pages-per-sequence), so the engine makes
        progress and every request eventually completes."""
        while True:
            try:
                fresh = self.pool.alloc(slot, upto_pos)
                if fresh:
                    self._pt_stale = True
                return fresh
            except PoolExhausted:
                victims = [s for s in range(self.B)
                           if self.slot_req[s] is not None
                           and self.slot_admit[s] > self.slot_admit[slot]]
                if not victims:
                    self._preempt(slot)
                    return None
                self._preempt(max(victims, key=lambda s: self.slot_admit[s]))

    def _ensure_decode_pages(self, n_steps: int):
        """Pre-sync allocation: back every live slot's worst-case position
        advance (``pos .. pos+n_steps-1``) so page-boundary crossings
        inside the fused scan never fault.  Oldest slots claim first."""
        dirty: list[int] = []
        order = sorted((s for s in range(self.B) if self.live[s]),
                       key=lambda s: self.slot_admit[s])
        for s in order:
            if not self.live[s]:        # preempted by an older claimant
                continue
            upto = min(int(self.pos[s]) + n_steps - 1, self.max_seq - 1)
            fresh = self._reclaim_for(s, upto)
            if fresh:
                dirty.extend(fresh)
        self._flush_dirty_pages(dirty)
        self._sync_page_table()

    # ------------------------------------------------------------------
    def _start_decode(self, slot: int):
        """Arm a slot for (forced-)decode after 0..pf_target prefilled."""
        q = int(self.pf_target[slot])
        self.tokens[slot, 0] = self.prompt_buf[slot, q]
        self.cursor[slot] = q + 1
        self.pos[slot] = q
        self.live[slot] = True

    def _reject(self, req: Request, reason: str):
        req.failed = True
        req.done = True
        req.fail_reason = reason
        self.stats["rejected"] += 1

    def _admit(self):
        admitted = np.zeros((self.B,), bool)
        free_slots = (s for s in range(self.B) if self.slot_req[s] is None)
        while self.queue:
            req = self.queue[0]
            prompt = np.asarray(req.prompt, np.int32)
            L = prompt.shape[0]
            if not 1 <= L < self.max_seq:
                # typed rejection instead of the seed's assert: the
                # engine keeps serving everyone else
                self.queue.popleft()
                self._reject(req, f"prompt length {L} outside "
                                  f"[1, max_seq={self.max_seq})")
                continue
            if self.pool is not None \
                    and self.pool.pages_for(L) > self.pool.free_pages:
                break   # memory-aware: head request's footprint must fit
                        # (FIFO — later requests don't jump the queue)
            slot = next(free_slots, None)
            if slot is None:
                break
            self.queue.popleft()
            self.slot_req[slot] = req
            self.slot_admit[slot] = self._admitted
            self.prompt_buf[slot, :L] = prompt
            self.plen[slot] = L
            self.remaining[slot] = req.max_new_tokens
            # per-request PRNG stream, independent of slot placement
            self.keys[slot] = np.asarray(
                jax.random.fold_in(self._root_key, self._admitted))
            self._admitted += 1
            self.stats["admissions"] += 1
            self.temp[slot] = req.temperature
            self.topk[slot] = req.top_k
            C = self.prefill_chunk
            # full chunks only (single prefill trace; conv state stays
            # exact) — the remainder plus the last prompt token go
            # through forced decode, so the first sampled token's
            # logits always come from the decode path
            q = ((L - 1) // C) * C if C > 0 else 0
            self.pf_target[slot] = q
            self.pf_done[slot] = 0
            if q:
                self.live[slot] = False   # decode starts after prefill
            else:
                self._start_decode(slot)
            admitted[slot] = True
        if admitted.any() and self._has_state:
            mask = jnp.asarray(admitted)
            leaves, treedef = jax.tree_util.tree_flatten(self.cache)
            if self.kv_layout == "dense":
                # full-cache round trip (KV stripes ride along unchanged)
                self.cache = jax.tree_util.tree_unflatten(
                    treedef, _zero_leaves(leaves, mask, self._state_axes))
                self.stats["admit_cache_elems"] += self._cache_elems
            else:
                state_axes = tuple(self._state_axes[i]
                                   for i in self._state_idx)
                state = _zero_leaves([leaves[i] for i in self._state_idx],
                                     mask, state_axes)
                for i, leaf in zip(self._state_idx, state, strict=True):
                    leaves[i] = leaf
                self.cache = jax.tree_util.tree_unflatten(treedef, leaves)
                self.stats["admit_cache_elems"] += self._state_elems
        occupied = sum(r is not None for r in self.slot_req)
        self.stats["peak_occupied"] = max(self.stats["peak_occupied"],
                                          occupied)

    def _pump_prefill(self):
        C = self.prefill_chunk
        if not C:
            return
        pending = [s for s in range(self.B)
                   if self.slot_req[s] is not None
                   and self.pf_done[s] < self.pf_target[s]]
        if not pending:
            return
        budget = self.max_prefill_tokens_per_sync
        pending.sort(key=lambda s: self.slot_admit[s])
        take = []
        dirty: list[int] = []
        for s in pending:
            if budget is not None and take and (len(take) + 1) * C > budget:
                break   # bound per-sync prefill work (at least one slot)
            if self.pool is not None:
                fresh = self._reclaim_for(s, int(self.pf_done[s]) + C - 1)
                if fresh is None:
                    continue            # preempted (youngest) — requeued
                dirty.extend(fresh)
            take.append(s)
        if self.pool is not None:
            self._flush_dirty_pages(dirty)
            self._sync_page_table()
        if not take:
            return
        rows = {"prefill_rows": self.B * C,
                "prefill_rows_active": len(take) * C}
        for k, v in rows.items():
            self.stats[k] += v
        with _span("repro.serve.prefill", **rows):
            tok = np.zeros((self.B, C, *self.tokens.shape[2:]), np.int32)
            start = np.zeros((self.B,), np.int32)
            active = np.zeros((self.B,), bool)
            for s in take:
                d = int(self.pf_done[s])
                tok[s] = self.prompt_buf[s, d:d + C]
                start[s] = d
                active[s] = True
            self.cache = _prefill_chunk(
                self.cfg, self.params, self.cache, jnp.asarray(tok),
                jnp.asarray(start), jnp.asarray(active),
                self._pt_dev if self.pool is not None else None)
        for s in take:
            self.pf_done[s] += C
            if self.pf_done[s] >= self.pf_target[s]:
                self._start_decode(s)

    def _retire(self, slot: int):
        self.slot_req[slot].done = True
        self.slot_req[slot] = None
        self.slot_admit[slot] = -1
        if self.pool is not None:
            self.pool.free_slot(slot)   # O(1) free-on-retirement
            self._pt_stale = True

    # ------------------------------------------------------------------
    def _count_decode(self, steps: int, pos0, cursor0, emitted: int) -> dict:
        """Add one sync's decode rows to ``stats`` from the host arrays
        before and after it; returns the increments."""
        rows = {"decode_rows": self.B * steps,
                "decode_rows_live": int((self.pos - pos0).sum()),
                "decode_rows_forced": int((self.cursor - cursor0).sum()),
                "decode_rows_emitted": emitted}
        for k, v in rows.items():
            self.stats[k] += v
        return rows

    def _host_step(self) -> int:
        """Seed-style per-step host sync (benchmark baseline)."""
        if not self.live.any():
            return 0
        if self.pool is not None:
            self._ensure_decode_pages(1)
        if not self.live.any():         # everyone preempted (tiny pool)
            return 0
        pos0, cursor0 = self.pos.copy(), self.cursor.copy()
        emitted = 0
        logits, self.cache = _decode_once(
            self.cfg, self.params, self.cache, jnp.asarray(self.tokens),
            jnp.asarray(self.pos), jnp.asarray(self.live),
            self._pt_dev if self.pool is not None else None)
        self.steps += 1
        logits_np = np.asarray(logits.astype(jnp.float32))
        finished = 0
        for slot in range(self.B):
            req = self.slot_req[slot]
            if req is None or not self.live[slot]:
                continue
            self.pos[slot] += 1
            if self.cursor[slot] < self.plen[slot]:
                self.tokens[slot, 0] = self.prompt_buf[slot,
                                                       self.cursor[slot]]
                self.cursor[slot] += 1
                continue
            key, sub = jax.random.split(jnp.asarray(self.keys[slot]))
            self.keys[slot] = np.asarray(key)
            # eager per-slot sampling on purpose: this mode is the seed
            # engine's cost profile (the benchmark baseline), minus its
            # correctness bugs — sample() itself now takes temperature as
            # a traced operand so jitted callers never retrace on it
            tok = np.asarray(sample(
                jnp.asarray(logits_np[slot]), sub,
                temperature=jnp.float32(req.temperature), top_k=req.top_k))
            req.output.append(np.array(tok))
            emitted += 1
            self.remaining[slot] -= 1
            self.tokens[slot, 0] = tok
            if self.remaining[slot] <= 0 or self.pos[slot] >= self.max_seq - 1:
                self.live[slot] = False
                self._retire(slot)
                finished += 1
        self._count_decode(1, pos0, cursor0, emitted)
        return finished

    def _fused_sync(self) -> int:
        """One fused dispatch of ``steps_per_sync`` steps + one host sync."""
        if not self.live.any():
            return 0
        if self.pool is not None:
            self._ensure_decode_pages(self.steps_per_sync)
        if not self.live.any():         # everyone preempted (tiny pool)
            return 0
        pos0, cursor0 = self.pos, self.cursor
        with _span("repro.serve.decode_dispatch"):
            state = {"tokens": jnp.asarray(self.tokens),
                     "pos": jnp.asarray(self.pos),
                     "cursor": jnp.asarray(self.cursor),
                     "plen": jnp.asarray(self.plen),
                     "remaining": jnp.asarray(self.remaining),
                     "live": jnp.asarray(self.live),
                     "keys": jnp.asarray(self.keys)}
            self.cache, state, sampled, emit = _fused_steps(
                self.cfg, self.steps_per_sync, self.params, self.cache, state,
                jnp.asarray(self.prompt_buf), jnp.asarray(self.temp),
                jnp.asarray(self.topk),
                self._pt_dev if self.pool is not None else None,
                self._paged_meta)
        self.steps += self.steps_per_sync
        with _span("repro.serve.decode_wait"):
            sampled = np.asarray(sampled)
            emit = np.asarray(emit)
            self.tokens = np.array(state["tokens"])
            self.pos = np.array(state["pos"])
            self.cursor = np.array(state["cursor"])
            self.remaining = np.array(state["remaining"])
            self.keys = np.array(state["keys"])
            new_live = np.array(state["live"])
        rows = self._count_decode(self.steps_per_sync, pos0, cursor0,
                                  int(emit.sum()))
        with _span("repro.serve.unpack", **rows):
            for s in range(self.steps_per_sync):
                for slot in np.nonzero(emit[s])[0]:
                    self.slot_req[slot].output.append(
                        np.array(sampled[s, slot]))
            finished = 0
            for slot in np.nonzero(self.live & ~new_live)[0]:
                self._retire(slot)
                finished += 1
            self.live = new_live
        return finished

    def step(self) -> int:
        """Admission + one stepping round; returns #requests finished.

        In fused mode one round is ``steps_per_sync`` decode steps."""
        with _span("repro.serve.step"):
            with _span("repro.serve.admit"):
                self._admit()
            self._pump_prefill()
            return self._fused_sync() if self.mode == "fused" \
                else self._host_step()

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.steps < max_steps:
            self.step()
        return self.steps
