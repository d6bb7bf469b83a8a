"""Random weights of a dense GQA decoder, made on the device from the seed.

The weights are the benchmark's, not the program's: this module names each
tensor, draws it from its own key, and lays the result out the way the
program takes its parameters.  The reference reads the same tensors under
the same names, so the program and the reference see identical numbers and
neither takes anything from the other.

Scales: the embedding (tied head) N(0, 0.02); every matrix N(0, 1/fan_in);
every norm weight 1 + N(0, 0.1), so that a norm that drops its weight shows.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from harness.arith import Arch

# reference name -> path in the program's parameter tree; layer tensors are
# stacked on a leading layer axis under segment 0
LAYER_PATHS = {
    "ln1": ("ln1",),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "q_norm": ("mixer", "q_norm"), "k_norm": ("mixer", "k_norm"),
    "ln2": ("ln2",),
    "up": ("ffn", "wi"), "gate": ("ffn", "wg"), "down": ("ffn", "wo"),
}
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size up to 64 bits as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def seed_key(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def layout(a: Arch, *, tied: bool, qk_norm: bool) -> dict:
    """Reference name -> (shape, per_layer) of every tensor."""
    d, hd = a.d_model, a.head_dim
    out = {"embed": ((a.vocab, d), False), "final_norm": ((d,), False)}
    if not tied:
        out["head"] = ((d, a.vocab), False)
    layer = {"ln1": (d,), "wq": (d, a.heads * hd), "wk": (d, a.kv_heads * hd),
             "wv": (d, a.kv_heads * hd), "wo": (a.heads * hd, d),
             "ln2": (d,), "up": (d, a.d_ff), "gate": (d, a.d_ff),
             "down": (a.d_ff, d)}
    if qk_norm:
        layer.update(q_norm=(hd,), k_norm=(hd,))
    out.update({k: (s, True) for k, s in layer.items()})
    return out


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        x = 1.0 + 0.1 * z
    elif name == "embed":
        x = 0.02 * z
    else:
        x = z * (shape[-2] ** -0.5)
    return x.astype(dtype)


def make_generator(a: Arch, *, tied: bool, qk_norm: bool, dtype):
    """A jitted ``words -> {name: array}`` that makes every tensor in one
    call; layer tensors are ``[layers, ...]``, drawn layer by layer."""
    spec = layout(a, tied=tied, qk_norm=qk_norm)

    @jax.jit
    def generate(words):
        base = seed_key(words)
        out = {}
        for name, (shape, per_layer) in spec.items():
            key = jax.random.fold_in(base, zlib.crc32(name.encode()))
            if per_layer:
                out[name] = jax.lax.map(
                    lambda i, key=key, name=name, shape=shape: _draw(
                        jax.random.fold_in(key, i), name, shape, dtype),
                    jnp.arange(a.layers))
            else:
                out[name] = _draw(key, name, shape, dtype)
        return out

    return generate


def program_tree(w: dict) -> dict:
    """The program's parameter tree over the same arrays (no copies)."""
    seg: dict = {}
    for name, path in LAYER_PATHS.items():
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


def from_program_tree(tree) -> dict:
    """{reference name: array} of a tree laid out as the program's
    parameters (the inverse of ``program_tree``)."""
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
    if "lm_head" in tree:
        out["head"] = tree["lm_head"]
    seg = tree["segments"][0]
    for name, path in LAYER_PATHS.items():
        node = seg
        for p in path:
            node = node.get(p) if isinstance(node, dict) else None
            if node is None:
                break
        if node is not None:
            out[name] = node
    return out
