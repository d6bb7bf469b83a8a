"""Random weights, made on the device from the seed.

The weights are the benchmark's, not the program's: the configuration's
reference module lays out each tensor (name, shape, init), this module
draws each from its own key, and the module maps the result onto the
program's parameter tree.  The reference reads the same tensors under the
same names, so the program and the reference see identical numbers and
neither takes anything from the other.

Scales by the layout's ``init``: ``embed`` N(0, 0.02); ``fan_in``
N(0, 1/fan_in); ``norm`` 1 + N(0, 0.1), so that a norm that drops its
weight shows.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size up to 64 bits as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def seed_key(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def _draw(key, init: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "norm":
        x = 1.0 + 0.1 * z
    elif init == "embed":
        x = 0.02 * z
    elif init == "fan_in":
        x = z * (shape[-2] ** -0.5)
    else:
        raise ValueError(f"unknown init {init!r}")
    return x.astype(dtype)


def make_generator(layout: dict, layers: int, dtype):
    """A jitted ``words -> {name: array}`` that makes every tensor of
    ``layout`` ({name: ``Tensor``}) in one call, in ``dtype`` unless the
    tensor names its own; a per-layer tensor is ``[layers, ...]``, drawn
    layer by layer."""

    @jax.jit
    def generate(words):
        base = seed_key(words)
        out = {}
        for name, t in layout.items():
            key = jax.random.fold_in(base, zlib.crc32(name.encode()))
            dt = jnp.dtype(t.dtype) if t.dtype else dtype
            if t.per_layer:
                out[name] = jax.lax.map(
                    lambda i, key=key, t=t, dt=dt: _draw(
                        jax.random.fold_in(key, i), t.init, t.shape, dt),
                    jnp.arange(layers))
            else:
                out[name] = _draw(key, t.init, t.shape, dt)
        return out

    return generate


def check_matches(tree, expected) -> None:
    """Raise unless ``tree`` has the structure, shapes and dtypes of the
    program's own parameter tree ``expected`` (from ``jax.eval_shape``)."""
    got = jax.tree_util.tree_flatten_with_path(tree)[0]
    want = jax.tree_util.tree_flatten_with_path(expected)[0]
    g = {jax.tree_util.keystr(p): (tuple(x.shape), x.dtype) for p, x in got}
    w = {jax.tree_util.keystr(p): (tuple(x.shape), x.dtype) for p, x in want}
    if g != w:
        diff = sorted((k, g.get(k), w.get(k)) for k in set(g) | set(w)
                      if g.get(k) != w.get(k))
        raise RuntimeError("the benchmark's weights do not match the "
                           f"program's parameter tree: {diff}")
