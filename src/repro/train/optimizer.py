"""Optimizers (no external deps): AdamW with fp32 master weights, and
Adafactor (factored second moment) for parameter-heavy models.

State layout is a plain pytree so ZeRO-1 sharding (sharding/zero.py) can
assign per-leaf shardings, and the checkpointer can save/restore it like
any other tree.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AdamW:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1

    def init(self, params):
        f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "m": jax.tree_util.tree_map(f32, params),
            "v": jax.tree_util.tree_map(f32, params),
            "master": jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), params),
            "count": jnp.zeros((), jnp.int32),
        }

    @jax.named_scope("adamw")
    def update(self, grads, state, params, lr):
        c = state["count"] + 1
        b1c = 1 - self.b1 ** c.astype(jnp.float32)
        b2c = 1 - self.b2 ** c.astype(jnp.float32)

        def upd(g, m, v, master):
            g = g.astype(jnp.float32)
            m = self.b1 * m + (1 - self.b1) * g
            v = self.b2 * v + (1 - self.b2) * jnp.square(g)
            mh, vh = m / b1c, v / b2c
            step = mh / (jnp.sqrt(vh) + self.eps) + self.weight_decay * master
            master = master - lr * step
            return m, v, master

        out = jax.tree_util.tree_map(upd, grads, state["m"], state["v"],
                                     state["master"])
        m = jax.tree_util.tree_map(lambda t: t[0], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
        v = jax.tree_util.tree_map(lambda t: t[1], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
        master = jax.tree_util.tree_map(lambda t: t[2], out,
                                        is_leaf=lambda t: isinstance(t, tuple))
        new_params = jax.tree_util.tree_map(
            lambda mp, p: mp.astype(p.dtype), master, params)
        return new_params, {"m": m, "v": v, "master": master, "count": c}


# ---------------------------------------------------------------------------
# Adafactor (factored v; no master copy -> ~4 bytes/param state)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Adafactor:
    decay: float = 0.8
    eps: float = 1e-30
    clip_threshold: float = 1.0
    weight_decay: float = 0.0

    def _factored(self, shape) -> bool:
        return len(shape) >= 2

    def init(self, params):
        def per(p):
            if self._factored(p.shape):
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return {"v": jnp.zeros(p.shape, jnp.float32)}

        return {
            "v": jax.tree_util.tree_map(per, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(self, grads, state, params, lr):
        c = state["count"] + 1
        rho = 1.0 - c.astype(jnp.float32) ** -self.decay

        def upd(g, v, p):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + self.eps
            if "vr" in v:
                vr = rho * v["vr"] + (1 - rho) * jnp.mean(g2, axis=-1)
                vc = rho * v["vc"] + (1 - rho) * jnp.mean(g2, axis=-2)
                denom = jnp.mean(vr, axis=-1, keepdims=True)
                u = (g / jnp.sqrt(vr / denom)[..., None]
                     / jnp.sqrt(vc)[..., None, :])
                nv = {"vr": vr, "vc": vc}
            else:
                nv = {"v": rho * v["v"] + (1 - rho) * g2}
                u = g / jnp.sqrt(nv["v"])
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
            u = u / jnp.maximum(1.0, rms / self.clip_threshold)
            pf = p.astype(jnp.float32)
            pf = pf - lr * u - lr * self.weight_decay * pf
            return pf.astype(p.dtype), nv

        out = jax.tree_util.tree_map(upd, grads, state["v"], params)
        is_pair = lambda t: isinstance(t, tuple)
        new_params = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair)
        v = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair)
        return new_params, {"v": v, "count": c}


def get_optimizer(name: str, **kw):
    if name == "adamw":
        return AdamW(**kw)
    if name == "adafactor":
        return Adafactor(**kw)
    raise KeyError(name)
