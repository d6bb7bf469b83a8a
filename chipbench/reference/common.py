"""What every plain reference shares: the interface the harness reads a
reference module by, narrow-float rounding for the controls, and AdamW.

A reference module under ``chipbench/reference/`` is named by a
configuration file's ``"reference"`` and provides ``build(config) ->
model``, where ``config`` is that file as a dict.  The model gives:

* ``layers`` and ``vocab``;
* ``layout()``: {name: ``Tensor``}, every tensor of the weights, in the
  order they are drawn;
* ``to_program(w)`` and ``from_program(tree)``: the program's parameter
  tree over the same arrays, and back to {name: array};
* ``final_hidden(w, tokens, weight_dtype=None)`` and ``head_matrix(w,
  weight_dtype=None)``: the float32 forward pass at the ``highest``
  precision, for the serving comparison;
* ``loss(w, tokens, weight_dtype=None)``: mean next-token cross-entropy,
  for the training comparison;
* the arithmetic of its work: ``train_step_flops(batch, seq)``,
  ``decode_token_flops(kv_len)`` and each kernel's (operations, bytes).

It imports nothing of the program under test.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Tensor:
    """One tensor of the weights.  ``shape`` is one layer's for a
    ``per_layer`` tensor, which is stacked ``[layers, ...]`` and drawn layer
    by layer.  ``init``: ``norm`` 1 + N(0, 0.1), ``embed`` N(0, 0.02),
    ``fan_in`` N(0, 1/shape[-2]).  ``split``: the axis of ``shape`` along
    which a reference spread over several chips lays it out, or None to
    keep it whole on each.  ``dtype``: the type the program keeps it in,
    where that is not the configuration's (a router kept in float32)."""
    shape: tuple
    per_layer: bool
    init: str
    split: int | None = None
    dtype: str | None = None


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
