"""Token samplers: greedy / temperature / top-k.

``sample`` is branch-free in ``temperature`` so it can be jitted with the
temperature as a *traced* argument — per-request settings then never
retrigger compilation (the seed version python-branched on the float, so
every distinct temperature was a fresh trace).  ``sample_batch`` is the
slot-vectorised variant the serving engine uses: per-slot RNG keys and
per-slot temperature/top-k vectors, one fused dispatch for the whole batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _greedy(lf):
    """argmax with a tie-break that is stable across compiled programs.

    XLA's argmax does not guarantee which index wins an *exact* tie — two
    fusions of the same logits can disagree, which breaks the engine's
    batched-vs-solo identity guarantee.  max() is order-independent and the
    integer min over tied indices is unique, so this is deterministic."""
    m = jnp.max(lf, axis=-1, keepdims=True)
    v = lf.shape[-1]
    idx = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32), lf.shape)
    return jnp.min(jnp.where(lf == m, idx, v), axis=-1).astype(jnp.int32)


@jax.named_scope("sampler")
def sample(logits, rng, *, temperature=0.0, top_k: int = 0):
    """logits [..., V] -> token ids [...].

    ``temperature`` may be a python float or a traced f32 scalar;
    temperature == 0 selects greedy argmax.  ``top_k`` stays a static int
    (0 disables)."""
    lf = logits.astype(jnp.float32)
    greedy = _greedy(lf)
    temp = jnp.asarray(temperature, jnp.float32)
    scaled = lf / jnp.maximum(temp, 1e-6)
    if top_k:
        vals, _ = jax.lax.top_k(scaled, top_k)
        cutoff = vals[..., -1:]
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    drawn = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, drawn, greedy)


@jax.named_scope("sampler")
def sample_batch(logits, keys, temperature, top_k):
    """Per-slot batched sampling for the serving engine.

    logits: [B, V] or [B, cb, V]; keys: [B, 2] uint32 (one PRNG key per
    slot — concurrent users draw from independent streams); temperature:
    [B] f32 (0 = greedy); top_k: [B] int32 (0 = disabled, traced so mixed
    per-request settings share one compilation).  Returns int32 [B(,cb)]."""
    lf = logits.astype(jnp.float32)
    B, V = lf.shape[0], lf.shape[-1]
    lead = (B,) + (1,) * (lf.ndim - 2)       # broadcast per-slot scalars
    greedy = _greedy(lf)

    # traced per-slot top-k: k-th largest value as cutoff via a descending
    # sort (top_k <= 0 keeps everything)
    desc = jnp.flip(jnp.sort(lf, axis=-1), axis=-1)
    kidx = (jnp.clip(top_k, 1, V) - 1).reshape(*lead, 1)
    kidx = jnp.broadcast_to(kidx, (*lf.shape[:-1], 1))
    cutoff = jnp.take_along_axis(desc, kidx, axis=-1)
    use_k = (top_k > 0).reshape(*lead, 1)
    masked = jnp.where(use_k & (lf < cutoff), -jnp.inf, lf)

    temp = temperature.astype(jnp.float32).reshape(*lead, 1)
    scaled = masked / jnp.maximum(temp, 1e-6)
    drawn = jax.vmap(
        lambda key, row: jax.random.categorical(key, row, axis=-1)
    )(keys, scaled).astype(jnp.int32)
    sel = (temperature > 0.0).reshape(lead)
    return jnp.where(sel, drawn, greedy)
