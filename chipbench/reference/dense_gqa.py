"""Plain float32 reference of a dense GQA decoder (Qwen3 / Llama family).

RMSNorm, rotary embeddings (half-split pairs), optional per-head qk-norm,
grouped-query causal attention, SwiGLU, and a head that is tied to the
embedding or its own.  Every matrix product runs in float32 at the
``highest`` precision.  It follows the published description and imports
nothing of the program under test.

``weight_dtype`` computes the same reference with every matrix rounded to a
narrower type (one float32 scale per output channel) before use: the
control that a comparison must fail.

Weights are a dict of named tensors (``embed``, ``final_norm``, optional
``head`` ``[d, V]``, and the layer tensors ``ln1 wq wk wv wo q_norm k_norm
ln2 up gate down``, each stacked ``[layers, ...]``).  Layers are applied one
at a time, so a model whose float32 weights would not fit runs from its
bfloat16 copy one layer at a time.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_NAMES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
               "up", "gate", "down")


@dataclass(frozen=True)
class Spec:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qk_norm: bool


NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def round_to(x, dtype):
    """``x`` rounded to the nearest value of the narrow float ``dtype``
    (saturating at its largest finite value), by arithmetic alone, so that
    no compiler can treat the round trip through ``dtype`` as a no-op."""
    fi = jnp.finfo(dtype)
    top = float(fi.max)
    a = jnp.minimum(jnp.abs(x), top)
    _, e = jnp.frexp(a)                                  # a = m * 2^e, m in [0.5, 1)
    e = jnp.maximum(e - 1, int(fi.minexp))               # subnormals share minexp
    step = jnp.ldexp(jnp.ones_like(a), e - int(fi.nmant))
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, top)


def fake_quant(x, axis: int, dtype):
    """``x`` rounded to ``dtype`` with one scale per slice along ``axis``
    (the reduced axis), returned in float32."""
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return round_to(x / scale, dtype) * scale


def _as_computed(name, x, weight_dtype):
    """A weight as the reference multiplies with it: float32, or a matrix
    rounded to ``weight_dtype`` per output channel (the embedding per row,
    which is the head's output channel when tied)."""
    if weight_dtype is None or name in NORMS or x.ndim < 2:
        return x.astype(jnp.float32)
    return fake_quant(x, -1 if name == "embed" else -2, weight_dtype)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, n, hd]; rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(spec: Spec, w, x):
    """Causal grouped-query attention of one sequence x [S, d]."""
    S = x.shape[0]
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    q = _mm(x, w["wq"]).reshape(S, H, hd)
    k = _mm(x, w["wk"]).reshape(S, K, hd)
    v = _mm(x, w["wv"]).reshape(S, K, hd)
    if spec.qk_norm:
        q = rmsnorm(q, w["q_norm"], spec.eps)
        k = rmsnorm(k, w["k_norm"], spec.eps)
    pos = jnp.arange(S)
    q, k = rope(q, pos, spec.rope_theta), rope(k, pos, spec.rope_theta)
    kv_of_head = jnp.arange(H) // (H // K)
    k, v = k[:, kv_of_head], v[:, kv_of_head]              # [S, H, hd]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return _mm(o.reshape(S, H * hd), w["wo"])


def block(spec: Spec, w, x):
    """One residual block on one sequence x [S, d]."""
    x = x + attention(spec, w, rmsnorm(x, w["ln1"], spec.eps))
    y = rmsnorm(x, w["ln2"], spec.eps)
    return x + _mm(jax.nn.silu(_mm(y, w["gate"])) * _mm(y, w["up"]),
                   w["down"])


@partial(jax.jit, static_argnums=(1,))
def head_matrix(w, weight_dtype=None):
    """The head [d, V] as the reference multiplies with it."""
    if "head" in w:
        return _as_computed("head", w["head"], weight_dtype)
    return _as_computed("embed", w["embed"], weight_dtype).T


@partial(jax.jit, static_argnums=(0, 3))
def _layer(spec: Spec, lw, h, weight_dtype):
    lw = {k: _as_computed(k, v, weight_dtype) for k, v in lw.items()}
    return jax.lax.map(lambda x: block(spec, lw, x), h)


@partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, weight_dtype):
    return jnp.take(_as_computed("embed", table, weight_dtype), tokens, axis=0)


def final_hidden(spec: Spec, w, tokens, weight_dtype=None):
    """Final-normed hidden states [n, S, d] of sequences ``tokens`` [n, S],
    applied layer by layer (one layer's weights in float32 at a time)."""
    h = _embed(w["embed"], tokens, weight_dtype)
    for i in range(spec.layers):
        lw = {k: w[k][i] for k in LAYER_NAMES if k in w}
        h = _layer(spec, lw, h, weight_dtype)
    return rmsnorm(h, w["final_norm"].astype(jnp.float32), spec.eps)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def loss(spec: Spec, w, tokens, weight_dtype=None):
    """Mean next-token cross-entropy of ``tokens`` [B, S] (float32 weights
    ``w``); each layer is recomputed in the backward pass.  With
    ``weight_dtype`` the forward multiplies with rounded matrices and the
    gradient passes the rounding straight through."""
    if weight_dtype is not None:
        w = {k: v + jax.lax.stop_gradient(_as_computed(k, v, weight_dtype) - v)
             for k, v in w.items()}
    layers = {k: w[k] for k in LAYER_NAMES if k in w}

    @jax.checkpoint
    def one(x, lw):
        return jax.vmap(lambda s: block(spec, lw, s))(x), None

    h = jnp.take(w["embed"], tokens, axis=0)
    h, _ = jax.lax.scan(one, h, layers)
    h = rmsnorm(h, w["final_norm"], spec.eps)
    lg = _mm(h[:, :-1], w["head"] if "head" in w else w["embed"].T)
    tgt = tokens[:, 1:]
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


@dataclass(frozen=True)
class AdamW:
    """AdamW with bias correction and decoupled weight decay, after global
    gradient-norm clipping."""
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0

    def clip(self, g):
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, self.clip_norm / (norm + 1e-9))
        return jax.tree_util.tree_map(lambda x: x * scale, g)

    def step(self, w, m, v, g, t):
        """One update at step ``t`` (1-based) with clipped gradients g."""
        m = jax.tree_util.tree_map(lambda a, b: self.b1 * a + (1 - self.b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: self.b2 * a + (1 - self.b2) * b * b, v, g)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        w = jax.tree_util.tree_map(
            lambda p, a, b: p - self.lr * ((a / c1) / (jnp.sqrt(b / c2) + self.eps)
                                           + self.weight_decay * p), w, m, v)
        return w, m, v
