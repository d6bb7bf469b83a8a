"""Plain float32 reference of a DeepSeek-V2 decoder: multi-head latent
attention (MLA) with YaRN rotary embeddings, leading dense layers, then
fine-grained mixture-of-experts layers with shared experts, and an untied
head.  Every matrix product runs in float32 at the ``highest`` precision.
It follows the published description (arXiv:2405.04434 and the model's
``modeling_deepseek.py``) and imports nothing of the program under test.

Attention is the non-absorbed form: each head's keys and values are made
from the latent ``c_kv`` and compared with the queries as in the paper's
equations, never folded into them.  Queries are projected directly (no
query compression).  YaRN: frequency i of the rope dimension d blends
f_extra = theta^(-2i/d) with f_inter = f_extra / factor by a ramp between
the correction indices low and high; cos and sin are scaled by
mscale(factor, mscale) / mscale(factor, mscale_all_dim), and the softmax
scale by mscale(factor, mscale_all_dim)^2.  Routing: softmax over all the
router's outputs, the top ``num_experts_per_tok`` kept with their
probabilities as weights (no renormalisation, scaling factor 1).

Departures from the published model:

* the share: a layer holds the routed experts ``held_experts`` names (one
  chip's share of an expert-parallel deployment); the router still scores
  all of them, and what the experts held elsewhere would add is left out;
  the shared experts count in full;
* random weights from the seed (``harness/weights.py``), not the trained
  ones;
* rotary pairs: features (2i, 2i+1) rotate in place; the published code
  rotates the same pairs and then lists the results evens first, a
  permutation that q and k share, so no score changes.

``weight_dtype`` computes the same reference with every matrix rounded to
a narrower type (one float32 scale per output channel) before use: the
control that a comparison must fail.

Weights are a dict of named tensors: ``embed``, ``final_norm``, ``head``
``[d, V]``; the leading dense layers' tensors ``dense_<name>``, each
``[first_k_dense, ...]`` and drawn whole; and the expert layers' tensors,
each stacked ``[layers, ...]`` and drawn layer by layer.  Layers are applied
one at a time, from the bfloat16 copy, in float32; attention in blocks of
queries, so that a long sequence's scores fit.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from reference.common import HIGHEST, Tensor, fake_quant

BF16 = 2
Q_BLOCK = 512           # queries whose scores are computed at once
NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
ATTN = ("ln1", "wq", "wdkv", "wkr", "kv_norm", "wuk", "wuv", "wo")
DENSE_FFN = ("ln2", "up", "gate", "down")
MOE_FFN = ("ln2", "router", "wi", "wg", "wo_e", "s_up", "s_gate", "s_down")


@dataclass(frozen=True)
class Spec:
    layers: int             # all layers
    dense: int              # leading dense layers
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int               # the dense layers' width
    d_expert: int
    experts: int            # the router's outputs
    top_k: int
    held_first: int
    held: int
    shared: int
    vocab: int
    theta: float
    eps: float
    yarn: tuple             # (factor, original positions, beta_fast,
                            #  beta_slow, mscale, mscale_all_dim) or ()


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _as_computed(name, x, weight_dtype):
    """A weight as the reference multiplies with it: float32, or a matrix
    rounded to ``weight_dtype`` per output channel (the embedding per
    row)."""
    base = name.removeprefix("dense_")
    if weight_dtype is None or base in NORMS or x.ndim < 2:
        return x.astype(jnp.float32)
    return fake_quant(x, -1 if name == "embed" else -2, weight_dtype)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(scale: float, k: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * k * math.log(scale) + 1.0


def rope_tables(spec: Spec):
    """(inverse frequencies [rope / 2], scale of cos and sin)."""
    d, theta = spec.rope, spec.theta
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f_extra = theta ** (-2.0 * i / d)
    if not spec.yarn:
        return f_extra, 1.0
    factor, orig, fast, slow, ms, ms_all = spec.yarn
    corr = lambda r: d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                   # the share of the original frequency
    inv = f_extra / factor * (1.0 - keep) + f_extra * keep
    return inv, yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)


def softmax_scale(spec: Spec) -> float:
    s = (spec.nope + spec.rope) ** -0.5
    if spec.yarn:
        s *= yarn_mscale(spec.yarn[0], spec.yarn[5]) ** 2
    return s


def rope(spec: Spec, x, pos):
    """x [S, n, rope]: rotate each pair (2i, 2i+1) by pos * inv_freq[i]."""
    inv, mscale = rope_tables(spec)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def attention(spec: Spec, w, x):
    """Causal MLA of one sequence x [S, d], per-head keys and values made
    from the latent; queries in blocks of ``Q_BLOCK``."""
    S = x.shape[0]
    H, dn, dr = spec.heads, spec.nope, spec.rope
    pos = jnp.arange(S)
    q = _mm(x, w["wq"]).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(spec, q[..., dn:], pos)], -1)
    ckv = rmsnorm(_mm(x, w["wdkv"]), w["kv_norm"], spec.eps)     # [S, r]
    k_pe = rope(spec, _mm(x, w["wkr"])[:, None, :], pos)          # [S, 1, dr]
    k = jnp.concatenate([_mm(ckv, w["wuk"]).reshape(S, H, dn),
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    v = _mm(ckv, w["wuv"]).reshape(S, H, spec.v_dim)
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0))).reshape(nb, qb, H, -1)
    starts = jnp.arange(nb) * qb

    def block(args):
        qs, start = args
        sc = jnp.einsum("qhd,khd->hqk", qs, k, precision=HIGHEST)
        sc = sc * softmax_scale(spec)
        causal = pos[None, :] <= (start + jnp.arange(qb))[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, (qp, starts)).reshape(nb * qb, H * spec.v_dim)[:S]
    return _mm(o, w["wo"])


def swiglu(x, up, gate, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def moe(spec: Spec, w, y):
    """The held experts' part of the routed FFN, plus the shared experts,
    for one sequence y [S, d]."""
    probs = jax.nn.softmax(_mm(y, w["router"]), axis=-1)          # [S, E]
    top, _ = jax.lax.top_k(probs, spec.top_k)
    chosen = probs >= top[:, -1:]
    weights = jnp.where(chosen, probs, 0.0)
    held = weights[:, spec.held_first:spec.held_first + spec.held]
    out = swiglu(y, w["s_up"], w["s_gate"], w["s_down"])
    for e in range(spec.held):
        out = out + held[:, e:e + 1] * swiglu(y, w["wi"][e], w["wg"][e],
                                              w["wo_e"][e])
    return out


def block(spec: Spec, w, x, dense: bool):
    """One residual block on one sequence x [S, d]."""
    x = x + attention(spec, w, rmsnorm(x, w["ln1"], spec.eps))
    y = rmsnorm(x, w["ln2"], spec.eps)
    if dense:
        return x + swiglu(y, w["up"], w["gate"], w["down"])
    return x + moe(spec, w, y)


@partial(jax.jit, static_argnums=(1,))
def head_matrix(w, weight_dtype=None):
    """The head [d, V] as the reference multiplies with it."""
    return _as_computed("head", w["head"], weight_dtype)


@partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(spec: Spec, lw, h, dense, weight_dtype):
    lw = {k.removeprefix("dense_"): _as_computed(k, v, weight_dtype)
          for k, v in lw.items()}
    return jax.lax.map(lambda x: block(spec, lw, x, dense), h)


@partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, weight_dtype):
    return jnp.take(_as_computed("embed", table, weight_dtype), tokens, axis=0)


def final_hidden(spec: Spec, w, tokens, weight_dtype=None):
    """Final-normed hidden states [n, S, d] of sequences ``tokens`` [n, S],
    applied layer by layer (one layer's weights in float32 at a time)."""
    h = _embed(w["embed"], tokens, weight_dtype)
    for i in range(spec.dense):
        lw = {f"dense_{k}": w[f"dense_{k}"][i] for k in ATTN + DENSE_FFN}
        h = _layer(spec, lw, h, True, weight_dtype)
    for i in range(spec.layers - spec.dense):
        lw = {k: w[k][i] for k in ATTN + MOE_FFN}
        h = _layer(spec, lw, h, False, weight_dtype)
    return rmsnorm(h, w["final_norm"].astype(jnp.float32), spec.eps)


# ---------------------------------------------------------------------------
# the model the harness reads
# ---------------------------------------------------------------------------
# reference name -> path in a layer of the program's parameter tree
PROGRAM_PATHS = {
    **{k: ("mixer", k) for k in ATTN if k not in ("ln1",)},
    "ln1": ("ln1",), "ln2": ("ln2",),
    "up": ("ffn", "wi"), "gate": ("ffn", "wg"), "down": ("ffn", "wo"),
    "router": ("ffn", "router"), "wi": ("ffn", "wi"), "wg": ("ffn", "wg"),
    "wo_e": ("ffn", "wo"), "s_up": ("ffn", "shared", "wi"),
    "s_gate": ("ffn", "shared", "wg"), "s_down": ("ffn", "shared", "wo"),
}


def _put(tree: dict, path, x) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = x


class Model:
    """A DeepSeek-V2 decoder of sizes ``spec``: the serving side of the
    harness's interface (``common.py``); no training cell runs it, so it
    has no ``from_program`` and its loss and training arithmetic raise.
    ``layers`` counts the expert layers, the ones the layout stacks;
    ``all_layers`` every layer."""

    def __init__(self, spec: Spec):
        self.spec = spec
        self.all_layers = spec.layers
        self.layers, self.vocab = spec.layers - spec.dense, spec.vocab

    # -- weights -------------------------------------------------------------
    def _every_layer(self) -> dict:
        """The shapes of the tensors every layer has: attention and the
        two norms."""
        s = self.spec
        d, H = s.d_model, s.heads
        return {"ln1": (d,), "wq": (d, H * (s.nope + s.rope)),
                "wdkv": (d, s.kv_rank), "wkr": (d, s.rope),
                "kv_norm": (s.kv_rank,), "wuk": (s.kv_rank, H * s.nope),
                "wuv": (s.kv_rank, H * s.v_dim), "wo": (H * s.v_dim, d),
                "ln2": (d,)}

    def layout(self) -> dict:
        s = self.spec
        d, E, f = s.d_model, s.held, s.d_expert
        init = lambda k: "norm" if k in NORMS else "fan_in"
        out = {"embed": Tensor((s.vocab, d), False, "embed", 0),
               "final_norm": Tensor((d,), False, "norm"),
               "head": Tensor((d, s.vocab), False, "fan_in", 1)}
        dense = {**self._every_layer(), "up": (d, s.d_ff),
                 "gate": (d, s.d_ff), "down": (s.d_ff, d)}
        for k, shape in dense.items():
            out[f"dense_{k}"] = Tensor((s.dense, *shape), False, init(k))
        sf = s.shared * f
        expert = {**self._every_layer(), "router": (d, s.experts),
                  "wi": (E, d, f), "wg": (E, d, f), "wo_e": (E, f, d),
                  "s_up": (d, sf), "s_gate": (d, sf), "s_down": (sf, d)}
        for k, shape in expert.items():
            out[k] = Tensor(shape, True, init(k),
                            dtype="float32" if k == "router" else None)
        return out

    def to_program(self, w: dict) -> dict:
        """The program's parameter tree over the same arrays (no copies):
        a segment of the dense layers and one of the expert layers."""
        segs: list = [{}, {}]
        for name, path in PROGRAM_PATHS.items():
            if f"dense_{name}" in w:
                _put(segs[0], path, w[f"dense_{name}"])
            if name in w and name not in ("up", "gate", "down"):
                _put(segs[1], path, w[name])
        return {"embed": w["embed"], "segments": segs,
                "final_norm": w["final_norm"], "lm_head": w["head"]}

    # -- the forward pass ----------------------------------------------------
    def final_hidden(self, w, tokens, weight_dtype=None):
        return final_hidden(self.spec, w, tokens, weight_dtype)

    def head_matrix(self, w, weight_dtype=None):
        return head_matrix(w, weight_dtype)

    def loss(self, w, tokens, weight_dtype=None):
        raise NotImplementedError("no training cell runs this reference")

    # -- arithmetic ----------------------------------------------------------
    def _absorbed_attention_params(self) -> int:
        """Weights one decoded token multiplies through in one layer's
        absorbed attention: q, the latent and rope projections, W_UK and
        W_UV folded into the query and the output, and o."""
        s = self.spec
        d, H = s.d_model, s.heads
        return (d * H * (s.nope + s.rope) + d * (s.kv_rank + s.rope)
                + H * s.nope * s.kv_rank + H * s.kv_rank * s.v_dim
                + H * s.v_dim * d)

    def decode_token_flops(self, kv_len: int) -> int:
        """Forward of one decoded token whose cache holds ``kv_len`` rows
        (itself included), head included.  Routed experts count at the
        share this layer holds, k·held/experts experts a token on average."""
        s = self.spec
        d, f = s.d_model, s.d_expert
        attn = self._absorbed_attention_params()
        dense = attn + 3 * d * s.d_ff
        routed = 3 * d * f * s.top_k * s.held / s.experts
        expert = attn + d * s.experts + 3 * d * f * s.shared + routed
        body = s.dense * dense + (s.layers - s.dense) * expert
        ops, _ = self.mla_decode_work(kv_len)
        return int(2 * (body + d * s.vocab) + s.layers * ops)

    def train_step_flops(self, batch: int, seq: int) -> int:
        raise NotImplementedError("no training cell runs this reference")

    def mla_decode_work(self, kv_len: int) -> tuple[int, int]:
        """(operations, bytes) of one layer's latent attention for one
        decoded token over ``kv_len`` cached rows: scores over the latent
        and rope parts and the latent values, every head; each live latent
        row read once."""
        s = self.spec
        flops = 2 * s.heads * (2 * s.kv_rank + s.rope) * kv_len
        return flops, kv_len * (s.kv_rank + s.rope) * BF16


def build(c: dict) -> Model:
    """The model of configuration file ``c``: the published keys of a
    DeepSeek-V2 ``config.json``, and ``held_experts`` ({first, count, of})
    for the routed experts a layer holds out of the router's ``of``."""
    if c.get("q_lora_rank") or c["tie_word_embeddings"] \
            or c["scoring_func"] != "softmax" or c["norm_topk_prob"] \
            or c["routed_scaling_factor"] != 1:
        raise ValueError("this reference covers direct queries, an untied "
                         "head and softmax routing, unnormalised, unscaled")
    held = c["held_experts"]
    if held["count"] != c["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    rs = c.get("rope_scaling")
    yarn = () if not rs else (
        float(rs["factor"]), int(rs["original_max_position_embeddings"]),
        float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
        float(rs["mscale_all_dim"]))
    return Model(Spec(
        layers=c["num_hidden_layers"], dense=c["first_k_dense_replace"],
        d_model=c["hidden_size"], heads=c["num_attention_heads"],
        kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
        rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        experts=held["of"], top_k=c["num_experts_per_tok"],
        held_first=held["first"], held=held["count"],
        shared=c["n_shared_experts"], vocab=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=c["rms_norm_eps"], yarn=yarn))
