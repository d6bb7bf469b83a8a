"""The comparisons that decide ``correct``.

Serving: the widest gap, over a seeded sample of finished requests, by
which a served token's logit lies below the best logit of the float32
reference at that position, in units of that position's logit standard
deviation (``served_gap``).  The reference runs once over each prompt with
its served tokens.  Its control reads the same gap for the token that the
reference with weights rounded to float8 (e4m3, one scale per output
channel) puts first.

Training: the gaps between the program's first steps and the reference's
(``loss_gap``, ``grad_gap``, ``change_gap``), each taken by the worst leaf
as the builder's rules set out; a leaf is one layer's slice of a tensor.
A cell on several chips spreads the reference's arrays over them with
plain ``jax.sharding``, each tensor along the axis its layout names.

The reference is the configuration's own module (``reference/``), read
through the model its ``build`` returns.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import HIGHEST, AdamW

CONTROL_DTYPE = jnp.float8_e4m3fn


@jax.jit
def _gaps(h, head, served, control_first):
    """Per-row gaps of served tokens and of the control's first tokens."""
    lg = jnp.matmul(h, head.astype(jnp.float32), precision=HIGHEST)
    best = jnp.max(lg, axis=-1)
    std = jnp.std(lg, axis=-1)
    pick = lambda t: jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
    return (best - pick(served)) / std, (best - pick(control_first)) / std


def _pad(seqs):
    S = max(len(s) for s in seqs)
    out = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def served_gap(model, w, samples, *, control: bool = False) -> dict:
    """``samples``: [(prompt, served tokens)].  Returns the widest gap of
    the served tokens and, with ``control``, that of the float8 control."""
    seqs = [np.concatenate([p, o[:-1]]).astype(np.int32) for p, o in samples]
    tokens = jnp.asarray(_pad(seqs))
    with jax.default_matmul_precision("highest"):
        h = model.final_hidden(w, tokens)
        head = model.head_matrix(w)
        if control:
            hq = model.final_hidden(w, tokens, CONTROL_DTYPE)
            head_q = model.head_matrix(w, CONTROL_DTYPE)
        worst, worst_c, n = 0.0, 0.0, 0
        for i, (p, o) in enumerate(samples):
            rows = slice(len(p) - 1, len(p) - 1 + len(o))
            served = jnp.asarray(np.asarray(o, np.int32))
            first = served
            if control:
                first = _argmax_rows(hq[i, rows], head_q)
            g, gc = _gaps(h[i, rows], head, served, first)
            worst = max(worst, float(jnp.max(g)))
            worst_c = max(worst_c, float(jnp.max(gc)))
            n += len(o)
    out = {"served_gap": worst, "served_tokens": n}
    if control:
        out["control_gap"] = worst_c
    return out


@jax.jit
def _argmax_rows(h, head):
    lg = jnp.matmul(h, head, precision=HIGHEST)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def slice_norms(w: dict, per_layer) -> dict:
    """{leaf name: norm}, each tensor named in ``per_layer`` (stacked
    ``[layers, ...]``) split into one leaf a layer."""
    out = {}
    for k in sorted(w):
        x = w[k]
        if k in per_layer:
            n = jax.jit(lambda a: jnp.sqrt(jnp.sum(
                jnp.square(a.astype(jnp.float32)),
                axis=tuple(range(1, a.ndim)))))(x)
            for i, v in enumerate(np.asarray(n)):
                out[f"{k}[{i}]"] = float(v)
        else:
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
    return out


def per_layer_names(model) -> set:
    return {k for k, t in model.layout().items() if t.per_layer}


def _worst(prog: dict, refr: dict, keep) -> float:
    base = float(np.median(list(refr.values())))
    return max(abs(prog[k] - refr[k]) / max(refr[k], base)
               for k in refr if keep(k))


def compare_train(prog: dict, refr: dict) -> dict:
    """Gaps of the program's readings ``prog`` against the reference's
    ``refr``; each holds ``losses`` [steps], ``grad`` and ``change``
    ({leaf: norm})."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], refr["losses"], strict=True))
    grad_gap = _worst(prog["grad"], refr["grad"], lambda k: True)
    # leaves the reference's gradient leaves at rounding move under Adam by
    # round-off alone: they are left out of the change
    floor = 1e-3 * float(np.median(list(refr["grad"].values())))
    change_gap = _worst(prog["change"], refr["change"],
                        lambda k: refr["grad"][k] >= floor)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def shardings(model, devices) -> dict | None:
    """{name: sharding} spreading each tensor of ``model``'s layout over
    ``devices`` along its ``split`` axis where that divides evenly, whole
    on each otherwise; None for one device."""
    if len(devices) < 2:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(devices), ("chips",))
    out = {}
    for k, t in model.layout().items():
        spec = [None] * (len(t.shape) + t.per_layer)
        if t.split is not None and t.shape[t.split] % len(devices) == 0:
            spec[t.split + t.per_layer] = "chips"
        out[k] = NamedSharding(mesh, PartitionSpec(*spec))
    return out


def _drop_half(new, old, per_layer: bool):
    """``new`` with the second half of its first even axis after the layer
    axis left at ``old``: half of each tensor's update lost, as when the
    exchange of one of two ZeRO-1 shards is left out."""
    axes = [a for a in range(int(per_layer), new.ndim) if new.shape[a] % 2 == 0]
    if not axes:
        return new
    a = axes[0]
    keep = jnp.arange(new.shape[a]) < new.shape[a] // 2
    keep = keep.reshape([-1 if i == a else 1 for i in range(new.ndim)])
    return jnp.where(keep, new, old)


def reference_step(model, lr: float, variant=None, sh=None):
    """The reference's jitted AdamW step ``(p, m, v, tokens, t) -> (p, m,
    v, loss, g)`` for ``variant`` (see ``reference_train``), keeping its
    state in the shardings ``sh`` where given.  The batch's mean loss and
    gradient are summed row by row so that one row's activations are live
    at a time."""
    opt = AdamW(lr=lr)
    per_layer = per_layer_names(model)
    dtype = CONTROL_DTYPE if variant == "float8" else None
    placed = {"out_shardings": (sh, sh, sh, None, sh)} if sh else {}

    def row_loss(p, row):
        return model.loss(p, row[None], dtype)

    @partial(jax.jit, donate_argnums=(0, 1, 2), **placed)
    def step(p, m, v, tokens, t):
        if variant == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        with jax.default_matmul_precision("highest"):
            def one(acc, row):
                loss, g = jax.value_and_grad(row_loss)(p, row)
                return (acc[0] + loss, jax.tree_util.tree_map(
                    jnp.add, acc[1], g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(jnp.zeros_like, p))
            (loss, g), _ = jax.lax.scan(one, zero, tokens)
            n = tokens.shape[0]
            loss = loss / n
            g = opt.clip(jax.tree_util.tree_map(lambda x: x / n, g))
            new, m, v = opt.step(p, m, v, g, t)
            if variant == "dropped_shards":
                new = {k: _drop_half(new[k], p[k], k in per_layer)
                       for k in new}
        return new, m, v, loss, g

    return step


def reference_train(model, w0_fn, batches, lr: float, *, variant=None,
                    devices=None):
    """The reference's readings over ``len(batches)`` AdamW steps from the
    float32 copy of the weights ``w0_fn()`` gives.  ``variant``: None (the
    reference), "float8" (the control: every matrix rounded to float8 in
    the forward), "half_batch" (a planted fault: the loss of the first half
    of each batch alone) or "dropped_shards" (a planted fault: half of each
    tensor's update lost).  ``devices``: the chips the arrays are spread
    over (default: the first device alone)."""
    per_layer = per_layer_names(model)
    sh = shardings(model, devices or jax.devices()[:1])
    w0 = w0_fn()
    w = {}
    for k in list(w0):
        x = w0.pop(k)
        w[k] = (jax.device_put(x, sh[k]) if sh else x).astype(jnp.float32)
    del w0
    step = reference_step(model, lr, variant, sh)
    p = {k: jnp.array(v) for k, v in w.items()}
    m = {k: jnp.zeros_like(v) for k, v in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    losses, grad = [], None
    for t, tokens in enumerate(batches, start=1):
        p, m, v, loss, g = step(p, m, v, jnp.asarray(tokens), jnp.float32(t))
        losses.append(float(loss))
        if t == 1:
            grad = slice_norms(g, per_layer)
        del g
    change = slice_norms({k: p[k] - w[k] for k in w}, per_layer)
    return {"losses": losses, "grad": grad, "change": change}
