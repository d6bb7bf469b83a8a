"""Mixture-of-Experts FFN.

One dispatch, told which routed experts the layer holds
(``MoEConfig.held_first``/``held_count``; all of them by default).  The
router scores all ``num_experts``; the (token, expert) assignments to held
experts are sorted by expert and run through ``jax.lax.ragged_dot`` with
group sizes taken from the routing, so no assignment is ever dropped and
no expert computes padding.  Assignments to experts held elsewhere add
nothing here: on one chip of an expert-parallel deployment the other
chips would compute them.

Where the installed rules split the held experts over mesh axes, each
device runs the dispatch over its own slice of them (shard_map) and one
psum over those axes combines the slices; two ways to place the tokens
(selected with REPRO_MOE, default 'gather'):

* 'gather' — every device sees every token (gathered over the DP axes),
  and the slices split over the 'experts' rule's axes.

* 'ep' — explicit expert parallelism.  Tokens stay sharded over the DP
  axes and are REPLICATED over 'model'; the held experts are split over
  'model'.  Each device routes its local tokens.

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
    E = m.num_held
    p = {
        "router": Param((d, m.num_experts), ("embed", None), init="scaled",
                        dtype="float32"),
        "wi": Param((E, d, m.d_ff_expert), ("experts", "embed", None),
                    init="scaled"),
        "wg": Param((E, d, m.d_ff_expert), ("experts", "embed", None),
                    init="scaled"),
        "wo": Param((E, m.d_ff_expert, d), ("experts", None, "embed"),
                    init="scaled"),
    }
    if m.num_shared_experts:
        p["shared"] = make_dense_ffn(
            cfg.replace(act="silu"), m.num_shared_experts * m.d_ff_expert)
    if m.scoring == "sigmoid":
        p["bias"] = Param((m.num_experts,), (None,), init="zeros",
                          dtype="float32")
    return p


@jax.named_scope("moe_route")
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


def _dispatch_combine(cfg, p, x2d, w, ids, *, first, count):
    """Dropless dispatch, expert FFNs and weighted combine over the experts
    [first, first + count), whose weights ``p["wi"|"wg"|"wo"]`` hold
    ``count`` experts.  ``first`` may be traced (a shard's offset inside
    shard_map).  Returns [T, d]: each token's sum over its held experts of
    routing weight times expert output.

    The rows are compacted to ``T * min(k, count)``: a token reaches the
    held experts at most that often, so nothing can overflow."""
    T, d = x2d.shape
    k = ids.shape[1]
    R = T * min(k, count)
    with jax.named_scope("moe_dispatch"):
        local = ids.reshape(-1) - first                    # [T*k]
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)                # others sort last
        order = jnp.argsort(key, stable=True)[:R]
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        xs = x2d[order // k]                               # [R, d]
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], sizes)) \
            * jax.lax.ragged_dot(xs, p["wi"], sizes)
        ys = jax.lax.ragged_dot(h, p["wo"], sizes)         # [R, d]
    with jax.named_scope("moe_combine"):
        # rows past sum(sizes) hold assignments held elsewhere: no group
        # computes them, and they are dropped here
        dest = jnp.where(jnp.arange(R) < sizes.sum(), order, T * k)
        y_flat = jnp.zeros((T * k, d), ys.dtype).at[dest].set(
            ys, mode="drop")
        y = jnp.einsum("tkd,tk->td", y_flat.reshape(T, k, d),
                       jnp.where(held, w.reshape(-1), 0.0).reshape(T, k))
    return y.astype(x2d.dtype)


def _moe_mode() -> str:
    return os.environ.get("REPRO_MOE", "gather")


def apply_moe(cfg, p, x2d):
    """x2d: [T, d]. Returns (y [T, d], aux_loss scalar)."""
    m = cfg.moe
    rules = current_rules()
    tokens_dp = _moe_mode() == "ep" and _ep_fits(cfg, x2d, rules)
    axes = ("model",) if tokens_dp else _expert_axes(cfg, rules)
    if axes:
        y, aux = _apply_split(cfg, p, x2d, rules, axes, tokens_dp)
    else:
        w, ids, aux = _route(cfg, p, x2d)
        y = _dispatch_combine(cfg, p, x2d, w, ids, first=m.held_first,
                              count=m.num_held)
    if m.num_shared_experts:
        y = y + apply_dense_ffn(cfg, p["shared"], x2d)
    return y, aux * m.aux_loss_coef


# ---------------------------------------------------------------------------
# the held experts split over mesh axes (shard_map)
# ---------------------------------------------------------------------------
def _expert_axes(cfg, rules) -> tuple[str, ...]:
    """The mesh axes the installed rules split the held experts over; none
    with no rules, or where the split would not be even."""
    if rules is None:
        return ()
    axes = tuple(rules.table.get("experts", ()))
    n = rules.axis_size(axes)
    return axes if n > 1 and cfg.moe.num_held % n == 0 else ()


def _ep_fits(cfg, x2d, rules) -> bool:
    """Whether the mesh has a 'model' axis that splits the held experts and
    data axes that split the tokens evenly."""
    if rules is None or "model" not in rules.mesh.axis_names:
        return False
    shape = rules.mesh.shape
    dp = 1
    for a in ("pod", "data"):
        dp *= shape.get(a, 1)
    return cfg.moe.num_held % shape["model"] == 0 and x2d.shape[0] % dp == 0


def _apply_split(cfg, p, x2d, rules, axes, tokens_dp):
    """Each device holds an even slice of the held experts along ``axes``,
    computes the routed output of its slice for the tokens it sees, and one
    psum over ``axes`` combines the slices.  With ``tokens_dp`` ('ep') the
    tokens stay sharded over the DP axes; otherwise ('gather') every device
    sees every token."""
    m = cfg.moe
    mesh = rules.mesh
    per = m.num_held // rules.axis_size(axes)
    dp_axes = tuple(a for a in ("pod", "data")
                    if tokens_dp and a in mesh.axis_names)
    if tokens_dp:
        x2d = shard(x2d, "batch", None)  # rows over DP, repl. model
    dp_spec = dp_axes[0] if len(dp_axes) == 1 else (dp_axes or None)
    ax_spec = axes[0] if len(axes) == 1 else axes
    bias = p.get("bias")

    def local(x_loc, router_w, bias_w, wi_l, wg_l, wo_l):
        pp = {"router": router_w, "wi": wi_l, "wg": wg_l, "wo": wo_l}
        if bias_w is not None:
            pp["bias"] = bias_w
        w, ids, aux = _route(cfg, pp, x_loc)
        shard_id = 0
        for a in axes:                   # row-major over the split axes
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)
        y_loc = _dispatch_combine(cfg, pp, x_loc, w, ids,
                                  first=m.held_first + shard_id * per,
                                  count=per)
        y = jax.lax.psum(y_loc, axes)
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return y, aux

    in_specs = (
        P(dp_spec, None),            # x2d
        P(None, None),               # router
        P(None) if bias is not None else None,
        P(ax_spec, None, None),      # wi  [E, d, ff]
        P(ax_spec, None, None),      # wg
        P(ax_spec, None, None),      # wo  [E, ff, d]
    )
    fn = partial(jax.shard_map, mesh=mesh,
                 in_specs=in_specs,
                 out_specs=(P(dp_spec, None), P()),
                 check_vma=False)(local)
    return fn(x2d, p["router"], bias, p["wi"], p["wg"], p["wo"])
