"""Mixture-of-Experts FFN.

Two dispatch strategies (selected with REPRO_MOE, default 'gather'):

* 'gather' — sorted-capacity dispatch under plain SPMD: the T*k (token,
  expert) assignments are sorted by expert id, ranked within expert via a
  running offset, and scattered into per-expert buffers [E, C, d].  Simple
  and correct, but XLA SPMD resolves the token->expert scatter with global
  gathers (the collective-bound baseline in §Perf).

* 'ep' — beyond-paper optimisation: explicit expert parallelism with
  shard_map.  Tokens stay sharded over the DP axes and are REPLICATED over
  'model'; experts are sharded over 'model'.  Each device top-k routes its
  local tokens, dispatches only to its local expert shard (local sort,
  local capacity), and a single psum over 'model' combines expert outputs.
  Per-MoE-layer collective traffic drops from O(T·d·E-shards gathers) to
  one [T_local, d] all-reduce.

Scoring: 'softmax' (classic top-k, switch-style aux loss) or 'sigmoid'
(DeepSeek-V3: sigmoid scores, top-k re-normalised).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import make_dense_ffn, apply_dense_ffn
from repro.models.params import Param
from repro.sharding.rules import current_rules, shard


def make_moe(cfg):
    d, m = cfg.d_model, cfg.moe
    p = {
        "router": Param((d, m.num_experts), ("embed", None), init="scaled",
                        dtype="float32"),
        "wi": Param((m.num_experts, d, m.d_ff_expert),
                    ("experts", "embed", None), init="scaled"),
        "wg": Param((m.num_experts, d, m.d_ff_expert),
                    ("experts", "embed", None), init="scaled"),
        "wo": Param((m.num_experts, m.d_ff_expert, d),
                    ("experts", None, "embed"), init="scaled"),
    }
    if m.num_shared_experts:
        p["shared"] = make_dense_ffn(
            cfg.replace(act="silu"), m.num_shared_experts * m.d_ff_expert)
    if m.scoring == "sigmoid":
        p["bias"] = Param((m.num_experts,), (None,), init="zeros",
                          dtype="float32")
    return p


def _route(cfg, p, x2d):
    """x2d: [T, d] -> (weights [T,k] f32, ids [T,k] i32, aux_loss f32)."""
    m = cfg.moe
    logits = x2d.astype(jnp.float32) @ p["router"]  # [T, E]
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["bias"][None, :]  # bias only affects selection
        _, ids = jax.lax.top_k(sel, m.top_k)
        w = jnp.take_along_axis(scores, ids, axis=1)
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
        probs = scores / (jnp.sum(scores, axis=1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, m.top_k)
    # switch-style load-balance loss: E * sum_e f_e * p_e
    T = x2d.shape[0]
    ones = jnp.ones((T, m.top_k), jnp.float32) / (T * m.top_k)
    frac_tokens = jnp.zeros((m.num_experts,), jnp.float32).at[ids].add(ones)
    frac_probs = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(frac_tokens * frac_probs)
    return w, ids.astype(jnp.int32), aux


def _capacity(cfg, T: int) -> int:
    m = cfg.moe
    cf = float(os.environ.get("REPRO_MOE_CF", m.capacity_factor))
    c = int(T * m.top_k * cf / m.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, at least 8


def _dispatch_combine(cfg, p, x2d, w, ids, *, num_experts, base_expert=0):
    """Sorted-capacity dispatch + expert einsum + weighted combine over the
    experts [base_expert, base_expert + num_experts).  Pure function of
    local data — usable both under SPMD ('gather') and inside shard_map
    ('ep', with per-shard expert slices).

    p_wi/p_wg/p_wo must already be the local expert slice when
    base_expert > 0 semantics are in play."""
    m = cfg.moe
    T, d = x2d.shape
    E, k = num_experts, m.top_k
    C = _capacity(cfg, T)

    flat_ids = ids.reshape(-1) - base_expert       # [T*k]; OOB -> dropped
    in_range = (flat_ids >= 0) & (flat_ids < E)
    flat_ids = jnp.where(in_range, flat_ids, E)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_eid = flat_ids[order]
    sorted_tok = order // k
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat_ids].add(1)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * k, dtype=jnp.int32) - offsets[sorted_eid]
    keep = (rank < C) & (sorted_eid < E)
    slot = jnp.where(keep, sorted_eid * C + rank, E * C)
    buf = jnp.zeros((E * C, d), x2d.dtype).at[slot].set(
        x2d[sorted_tok], mode="drop")
    buf = buf.reshape(E, C, d)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    h = jax.nn.silu(g) * h
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * C, d)

    safe_slot = jnp.where(keep, slot, 0)
    y_sorted = jnp.where(keep[:, None], y_buf[safe_slot], 0)
    y_flat = jnp.zeros((T * k, d), x2d.dtype).at[order].set(y_sorted)
    y = jnp.einsum("tkd,tk->td", y_flat.reshape(T, k, d),
                   w.astype(x2d.dtype))
    return y


def _moe_mode() -> str:
    return os.environ.get("REPRO_MOE", "gather")


def apply_moe(cfg, p, x2d):
    """x2d: [T, d]. Returns (y [T, d], aux_loss scalar)."""
    rules = current_rules()
    if _moe_mode() == "ep" and rules is not None \
            and "model" in rules.mesh.axis_names:
        return apply_moe_ep(cfg, p, x2d, rules)
    return apply_moe_gather(cfg, p, x2d)


def apply_moe_gather(cfg, p, x2d):
    """Baseline: SPMD sorted-capacity dispatch (paper-faithful layering)."""
    m = cfg.moe
    T, d = x2d.shape
    E, k = m.num_experts, m.top_k
    C = _capacity(cfg, T)
    w, ids, aux = _route(cfg, p, x2d)

    # ---- sorted-capacity dispatch -------------------------------------
    flat_ids = ids.reshape(-1)                      # [T*k]
    order = jnp.argsort(flat_ids, stable=True)      # sort by expert
    sorted_eid = flat_ids[order]
    sorted_tok = order // k
    counts = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
    offsets = jnp.cumsum(counts) - counts           # exclusive prefix
    rank = jnp.arange(T * k, dtype=jnp.int32) - offsets[sorted_eid]
    keep = rank < C
    slot = jnp.where(keep, sorted_eid * C + rank, E * C)  # OOB -> dropped
    buf = jnp.zeros((E * C, d), x2d.dtype).at[slot].set(
        x2d[sorted_tok], mode="drop")
    buf = shard(buf.reshape(E, C, d), "experts", None, None)

    # ---- expert compute (batched over E; shards as EP) -----------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    h = jax.nn.silu(g) * h
    h = shard(h, "experts", None, None)
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * C, d)

    # ---- combine back --------------------------------------------------
    safe_slot = jnp.where(keep, slot, 0)
    y_sorted = jnp.where(keep[:, None], y_buf[safe_slot], 0)
    y_flat = jnp.zeros((T * k, d), x2d.dtype).at[order].set(y_sorted)
    y = jnp.einsum("tkd,tk->td", y_flat.reshape(T, k, d), w.astype(x2d.dtype))

    if m.num_shared_experts:
        y = y + apply_dense_ffn(cfg, p["shared"], x2d)
    return y, aux * m.aux_loss_coef


# ---------------------------------------------------------------------------
# explicit expert parallelism (shard_map) — §Perf optimisation
# ---------------------------------------------------------------------------
def apply_moe_ep(cfg, p, x2d, rules):
    """Tokens DP-sharded / replicated over 'model'; experts sharded over
    'model'; one psum combines.  Falls back to 'gather' when the expert
    count does not divide the model axis."""
    m = cfg.moe
    mesh = rules.mesh
    ep = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    T, d = x2d.shape
    if m.num_experts % ep or T % dp_size:
        return apply_moe_gather(cfg, p, x2d)
    E_loc = m.num_experts // ep

    x2d = shard(x2d, "batch", None)  # pin layout: rows over DP, repl. model
    dp_spec = dp_axes[0] if len(dp_axes) == 1 else dp_axes

    router = p["router"]
    bias = p.get("bias")
    wi, wg, wo = p["wi"], p["wg"], p["wo"]

    def local(x_loc, router_w, bias_w, wi_l, wg_l, wo_l):
        pp = {"router": router_w, "wi": wi_l, "wg": wg_l, "wo": wo_l}
        if bias_w is not None:
            pp["bias"] = bias_w
        w, ids, aux = _route(cfg, pp, x_loc)
        shard_id = jax.lax.axis_index("model")
        y_loc = _dispatch_combine(cfg, pp, x_loc, w, ids,
                                  num_experts=E_loc,
                                  base_expert=shard_id * E_loc)
        y = jax.lax.psum(y_loc, "model")
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return y, aux

    in_specs = (
        P(dp_spec, None),            # x2d
        P(None, None),               # router
        P(None) if bias is not None else None,
        P("model", None, None),      # wi  [E, d, ff]
        P("model", None, None),      # wg
        P("model", None, None),      # wo  [E, ff, d]
    )
    fn = partial(jax.shard_map, mesh=mesh,
                 in_specs=in_specs,
                 out_specs=(P(dp_spec, None), P()),
                 check_vma=False)(local)
    y, aux = fn(x2d, router, bias, wi, wg, wo)
    if m.num_shared_experts:
        y = y + apply_dense_ffn(cfg, p["shared"], x2d)
    return y, aux * m.aux_loss_coef
