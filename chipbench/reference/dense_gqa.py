"""Plain float32 reference of a dense GQA decoder (Qwen3 / Llama family).

RMSNorm, rotary embeddings (half-split pairs), optional per-head qk-norm,
grouped-query causal attention, SwiGLU, and a head that is tied to the
embedding or its own.  Every matrix product runs in float32 at the
``highest`` precision.  It follows the published description and imports
nothing of the program under test.

``weight_dtype`` computes the same reference with every matrix rounded to a
narrower type (one float32 scale per output channel) before use: the
control that a comparison must fail.

``build(config)`` gives the model the harness reads (the interface is in
``common.py``): the layout, the map to the program's parameter tree, and
the operations and bytes the work needs, from the published sizes and the
real lengths of the work (adapted from the repository's
``configs/analysis.model_flops``, 6·N·T plus attention).  The arithmetic
counts what the algorithm needs, never what one implementation happens to
touch, so a roofline share reads the same work whatever implements the
kernel; a multiply-add is two operations.

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

from reference.common import HIGHEST, Tensor, fake_quant

BF16 = 2
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
    tied: bool = True


NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


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


# ---------------------------------------------------------------------------
# the model the harness reads
# ---------------------------------------------------------------------------
# reference name -> path in the program's parameter tree; layer tensors are
# stacked on a leading layer axis under segment 0
PROGRAM_PATHS = {
    "ln1": ("ln1",),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "q_norm": ("mixer", "q_norm"), "k_norm": ("mixer", "k_norm"),
    "ln2": ("ln2",),
    "up": ("ffn", "wi"), "gate": ("ffn", "wg"), "down": ("ffn", "wo"),
}


class Model:
    """A dense GQA decoder of sizes ``spec``: the reference's side of the
    harness's interface (``common.py``)."""

    def __init__(self, spec: Spec):
        self.spec = spec
        self.layers, self.vocab = spec.layers, spec.vocab
        self.heads, self.kv_heads = spec.heads, spec.kv_heads
        self.head_dim, self.d_model, self.d_ff = (spec.head_dim,
                                                  spec.d_model, spec.d_ff)

    # -- weights -------------------------------------------------------------
    def layout(self) -> dict:
        d, hd, s = self.d_model, self.head_dim, self.spec
        out = {"embed": Tensor((self.vocab, d), False, "embed", 0),
               "final_norm": Tensor((d,), False, "norm")}
        if not s.tied:
            out["head"] = Tensor((d, self.vocab), False, "fan_in", 1)
        layer = {"ln1": ((d,), None), "wq": ((d, self.heads * hd), 1),
                 "wk": ((d, self.kv_heads * hd), 1),
                 "wv": ((d, self.kv_heads * hd), 1),
                 "wo": ((self.heads * hd, d), 0), "ln2": ((d,), None),
                 "up": ((d, self.d_ff), 1), "gate": ((d, self.d_ff), 1),
                 "down": ((self.d_ff, d), 0)}
        if s.qk_norm:
            layer.update(q_norm=((hd,), None), k_norm=((hd,), None))
        for name, (shape, split) in layer.items():
            out[name] = Tensor(shape, True, "norm" if name in NORMS
                               else "fan_in", split)
        return out

    def to_program(self, w: dict) -> dict:
        """The program's parameter tree over the same arrays (no copies)."""
        seg: dict = {}
        for name, path in PROGRAM_PATHS.items():
            if name in w:
                node = seg
                for p in path[:-1]:
                    node = node.setdefault(p, {})
                node[path[-1]] = w[name]
        tree = {"embed": w["embed"], "segments": [seg],
                "final_norm": w["final_norm"]}
        if "head" in w:
            tree["lm_head"] = w["head"]
        return tree

    def from_program(self, tree) -> dict:
        """{reference name: array} of a tree laid out as the program's
        parameters (the inverse of ``to_program``)."""
        out = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
        if "lm_head" in tree:
            out["head"] = tree["lm_head"]
        seg = tree["segments"][0]
        for name, path in PROGRAM_PATHS.items():
            node = seg
            for p in path:
                node = node.get(p) if isinstance(node, dict) else None
                if node is None:
                    break
            if node is not None:
                out[name] = node
        return out

    # -- the forward pass and the loss ---------------------------------------
    def final_hidden(self, w, tokens, weight_dtype=None):
        return final_hidden(self.spec, w, tokens, weight_dtype)

    def head_matrix(self, w, weight_dtype=None):
        return head_matrix(w, weight_dtype)

    def loss(self, w, tokens, weight_dtype=None):
        return loss(self.spec, w, tokens, weight_dtype)

    # -- arithmetic ----------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one block: q, k, v and
        o projections and the three SwiGLU matrices."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    def attention_flops(self, context: int) -> int:
        """Scores and weighted values of one query token over ``context``
        keys, all layers."""
        return 4 * self.layers * self.heads * self.head_dim * context

    def decode_token_flops(self, kv_len: int) -> int:
        """Forward of one decoded token whose cache holds ``kv_len`` rows
        (itself included), head included."""
        return (2 * (self.layers * self.layer_matmul_params + self.head_params)
                + self.attention_flops(kv_len))

    def train_step_flops(self, batch: int, seq: int) -> int:
        """Forward and backward (3x the forward) of one causal-LM step on
        ``batch`` rows of ``seq`` tokens; the head scores ``seq - 1``
        positions.  Recomputation is not counted."""
        body = seq * 2 * self.layers * self.layer_matmul_params
        head = (seq - 1) * 2 * self.head_params
        attn = 4 * self.layers * self.heads * self.head_dim * seq * (seq + 1) // 2
        return 3 * batch * (body + head + attn)

    def decode_attention_work(self, kv_len: int) -> tuple[int, int]:
        """(operations, bytes) of one layer's decode attention for one slot:
        read ``kv_len`` rows of K and V, read q, write o."""
        flops = 4 * self.heads * self.head_dim * kv_len
        kv = 2 * kv_len * self.kv_heads * self.head_dim * BF16
        qo = 2 * self.heads * self.head_dim * BF16
        return flops, kv + qo

    def flash_attention_work(self, batch: int, seq: int) -> tuple[int, int]:
        """(operations, bytes) of one layer's causal attention forward over
        ``batch`` rows of ``seq`` tokens: read q, k, v, write o."""
        flops = 4 * batch * self.heads * self.head_dim * seq * (seq + 1) // 2
        qo = 2 * batch * seq * self.heads * self.head_dim * BF16
        kv = 2 * batch * seq * self.kv_heads * self.head_dim * BF16
        return flops, qo + kv


def build(c: dict) -> Model:
    """The model of configuration file ``c`` (published keys, and
    ``qk_norm`` for the per-head norm of q and k that Qwen3 has)."""
    return Model(Spec(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                      heads=c["num_attention_heads"],
                      kv_heads=c["num_key_value_heads"],
                      head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                      vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                      eps=c["rms_norm_eps"], qk_norm=c["qk_norm"],
                      tied=c["tie_word_embeddings"]))
