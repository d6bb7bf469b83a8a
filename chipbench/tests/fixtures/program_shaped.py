"""A stand-in reference for tests of the harness, found by name like any
reference module: its layout is the program's own parameter tree and its
loss the program's, computed in float32.  It shows that a family needs a
configuration file and a module with the interface of
``reference/common.py``, and no edit of the harness.  It is no reference
for a benchmark cell, whose reference imports nothing of the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import spec
from reference.common import Tensor


def _init(name: str, x) -> str:
    if "embed" in name:
        return "embed"
    return "fan_in" if x.ndim >= 2 and "norm" not in name else "norm"


class Model:
    def __init__(self, c: dict):
        from repro.models import lm

        self.cfg = spec.program_config(c)
        tree = jax.eval_shape(lambda: lm.init_lm(self.cfg,
                                                 jax.random.PRNGKey(0)))
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.names = [jax.tree_util.keystr(p) for p, _ in flat]
        own = jnp.dtype(c["dtype"])
        self._layout = {
            n: Tensor(tuple(x.shape), False, _init(n, x),
                      dtype=None if x.dtype == own else str(x.dtype))
            for n, (_, x) in zip(self.names, flat, strict=True)}
        self.params = sum(x.size for _, x in flat)
        self.layers, self.vocab = self.cfg.num_layers, self.cfg.vocab_size

    def layout(self) -> dict:
        return self._layout

    def to_program(self, w: dict):
        return jax.tree_util.tree_unflatten(self.treedef,
                                            [w[n] for n in self.names])

    def from_program(self, tree) -> dict:
        return dict(zip(self.names, jax.tree_util.tree_leaves(tree),
                        strict=True))

    def loss(self, w, tokens, weight_dtype=None):
        from repro.models import lm

        cfg = self.cfg.replace(dtype="float32")
        loss, _ = lm.train_loss(cfg, self.to_program(w), {"tokens": tokens},
                                remat=False)
        return loss

    def train_step_flops(self, batch: int, seq: int) -> int:
        return 6 * self.params * batch * seq


def build(c: dict) -> Model:
    return Model(c)
