"""The one generator every traffic mix goes through.

A mix is a data file, ``traffic/<name>.json``; this module turns it and a
seed into the program's inputs.  Sizes and inter-arrival gaps are drawn at
evenly spaced quantiles of the stated distributions and put in one fixed
order; the seed fills the prompts' tokens.  So every seed offers the same
work at the same times, and the spread between runs is the system's, not
the draw's: a window holds a few dozen requests, and when the seed ordered
them, which ones fell in the window moved the measured work by a sixth.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# a sorted sample of this many points per quantile stands in for a
# distribution with no closed-form quantile function (gamma)
_OVERSAMPLE = 64


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative integer)."""
    return np.random.default_rng([int(seed), *salt])


def _even(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` values at evenly spaced quantiles of ``dist``, ascending.

    ``dist`` is one of:
      {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
      {"dist": "uniform", "min": a, "max": b}          (integers a..b)
      {"dist": "gamma", "mean": m, "cv": c}            (m > 0, c > 0)
      {"dist": "const", "value": v}
    """
    kind = dist["dist"]
    u = _even(n)
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return np.floor(lo + u * (hi - lo + 1)).astype(np.int64)
    if kind == "gamma":
        shape = 1.0 / dist["cv"] ** 2
        scale = dist["mean"] / shape
        pool = np.sort(np.random.default_rng(0).gamma(
            shape, scale, size=n * _OVERSAMPLE))
        v = pool[(np.arange(n) * _OVERSAMPLE + _OVERSAMPLE // 2)]
        return v * (dist["mean"] / v.mean())    # exact offered rate
    if kind == "const":
        return np.full(n, dist["value"])
    raise ValueError(f"unknown distribution {kind!r}")


@dataclass
class Item:
    """One request as the load generator offers it."""
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    due: float = 0.0            # seconds after the schedule starts (open loop)


def _blocks(rng, dist: dict, n: int, block: int) -> np.ndarray:
    """``n`` values: consecutive blocks of ``block``, each a permutation
    (drawn from ``rng``) of the same ``block`` quantiles of ``dist``."""
    q = quantiles(dist, block)
    k = -(-n // block)
    return np.concatenate([rng.permutation(q) for _ in range(k)])[:n]


def requests(mix: dict, seed: int, n: int, vocab: int, *,
             block: int | None = None, stream: int = 1) -> list[Item]:
    """``n`` requests of ``mix`` for ``seed``: sizes in blocks of ``block``
    (default ``mix["block"]``; each block the same quantiles in one fixed
    order), uniform random token ids from the seed, and for an open loop
    the due times (gamma gaps in the same blocks at ``rate_per_s``, first
    due at 0).  ``stream`` picks an independent draw."""
    order = rng_for(0, stream)
    block = block or mix["block"]
    plen = _blocks(order, mix["prompt_tokens"], n, block)
    olen = _blocks(order, mix["output_tokens"], n, block)
    rng = rng_for(seed, stream)
    items = [Item(i, rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                  int(olen[i])) for i in range(n)]
    if mix["kind"] == "open_loop":
        gap = dict(mix["interarrival"], mean=1.0 / mix["rate_per_s"])
        gaps = _blocks(order, gap, n, block)
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        for it, t in zip(items, due, strict=True):
            it.due = float(t)
    return items


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Item]:
    """The open loop's requests in three phases: the preroll, the window
    and the drain after it.  Each phase is one block of its own, so the
    window holds the stated distributions' quantiles.  Due times count
    from the start of the preroll."""
    rate = mix["rate_per_s"]
    out: list[Item] = []
    start = 0.0
    for stream, span in enumerate((mix["preroll_s"], seconds,
                                   mix["drain_limit_s"]), start=1):
        n = max(1, round(span * rate))
        for it in requests(mix, seed, n, vocab, block=n, stream=stream):
            it.due += start
            it.index = len(out)
            out.append(it)
        start += span
    return out


def train_batches(mix: dict, seed: int, vocab: int):
    """The training feed, made on the device in one call: ``mix["batches"]``
    batches of ``[batch, seq_len]`` uniform random token ids, every row
    different."""
    import jax
    import jax.numpy as jnp

    from harness.weights import seed_key

    @jax.jit
    def make(words):
        key = jax.random.fold_in(seed_key(words), 0x7472)
        return jax.random.randint(
            key, (mix["batches"], mix["batch"], mix["seq_len"]), 0, vocab,
            dtype=jnp.int32)

    from harness.weights import seed_words
    return make(seed_words(seed))
