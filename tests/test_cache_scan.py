"""The step programs carry each segment's stacked cache through the layer
scan (``lm._cache_scan``): a dense attention layer writes its rows and
reads its layer in place, every other route reads its layer out of the
carry and writes it back.  Either way ``lm.decode_step`` and
``lm.prefill_chunk`` must give bit for bit what a plain loop over layers
gives, each layer's cache sliced out, run through the block function and
stacked again.  The loop is a scan as well: XLA compiles an unrolled loop
to other roundings of the bf16 intermediates."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import blocks as B
from repro.models import lm
from repro.models.layers import rmsnorm
from repro.models.params import init_params

SLOTS, MAX_SEQ, PAGE, CHUNK = 3, 32, 8, 4


def _per_layer(cfg, params, cache, h, run):
    """h through every layer: the scan slices each layer's cache out of
    the stack and stacks the new ones (scan xs and ys)."""
    new = []
    for seg, seg_p, seg_c in zip(lm.segments(cfg), params["segments"], cache,
                                 strict=True):
        def body(hh, xs, seg=seg):
            return run(seg, xs[0], hh, xs[1])

        h, c = jax.lax.scan(body, h, (seg_p, seg_c))
        new.append(c)
    return h, new


def decode_per_layer(cfg, params, batch, cache):
    pos, active = batch["pos"], batch.get("active")
    pt = batch.get("page_table")

    def run(seg, p, h, c):
        if seg.kind == "hybrid":
            return B.apply_super_block_decode(cfg, p, h, c, pos, seg.plan,
                                              active, pt)
        return B.apply_block_decode(cfg, p, h, c, pos, seg.mixer, seg.ffn,
                                    active, pt)

    h = lm.embed_tokens(cfg, params, batch["tokens"], batch)
    h, new = _per_layer(cfg, params, cache, h, run)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return lm.apply_head(cfg, params, h[:, -1]), new


def prefill_per_layer(cfg, params, batch, cache):
    start, active = batch["start"], batch.get("active")
    pt = batch.get("page_table")

    def run(seg, p, h, c):
        if seg.kind == "hybrid":
            return B.apply_super_block_prefill_chunk(cfg, p, h, c, start,
                                                     seg.plan, active, pt)
        return B.apply_block_prefill_chunk(cfg, p, h, c, start, seg.mixer,
                                           seg.ffn, active, pt)

    h = lm.embed_tokens(cfg, params, batch["tokens"], batch)
    return _per_layer(cfg, params, cache, h, run)[1]


def _setup(arch, paged):
    cfg = reduced_config(arch)
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    width = MAX_SEQ // PAGE
    descr = lm.make_cache(cfg, SLOTS, MAX_SEQ,
                          paged=(SLOTS * width, PAGE) if paged else None)
    # every row holds something, so a wrong layer or row shows
    leaves, tree = jax.tree_util.tree_flatten(
        init_params(descr, jax.random.PRNGKey(1)))
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    cache = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(keys, leaves, strict=True)])
    extra = {}
    if paged:   # each slot's pages scattered over the pool
        perm = np.random.default_rng(0).permutation(SLOTS * width)
        extra["page_table"] = jnp.asarray(
            perm.reshape(SLOTS, width).astype(np.int32))
    return cfg, params, cache, extra


def _tokens(cfg, n):
    shape = (SLOTS, n, cfg.num_codebooks) if cfg.num_codebooks \
        else (SLOTS, n)
    return jax.random.randint(jax.random.PRNGKey(3), shape, 0,
                              cfg.vocab_size)


def _same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# (arch, paged): each route of the layer scan
ROUTES = [("smollm-360m", False), ("smollm-360m", True),
          ("deepseek-v3-671b", False), ("mamba2-130m", False),
          ("jamba-v0.1-52b", False)]


@pytest.mark.parametrize("arch,paged", ROUTES)
def test_decode_step_matches_a_per_layer_loop(arch, paged, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    cfg, params, cache, extra = _setup(arch, paged)
    # a slot at the last row, an inactive slot, a slot mid-stripe
    batch = {"tokens": _tokens(cfg, 1),
             "pos": jnp.array([MAX_SEQ - 1, 5, 11], jnp.int32),
             "active": jnp.array([True, False, True]), **extra}
    got = jax.jit(lambda p, b, c: lm.decode_step(cfg, p, b, c))(
        params, batch, cache)
    want = jax.jit(lambda p, b, c: decode_per_layer(cfg, p, b, c))(
        params, batch, cache)
    _same(got, want)


@pytest.mark.parametrize("arch,paged", ROUTES)
def test_prefill_chunk_matches_a_per_layer_loop(arch, paged, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    cfg, params, cache, extra = _setup(arch, paged)
    # the dense stripe drops rows at or past MAX_SEQ: slot 0's chunk
    # starts on the last row (a page table has no row past it to take)
    first = MAX_SEQ - 1 if not paged else MAX_SEQ - CHUNK
    batch = {"tokens": _tokens(cfg, CHUNK),
             "start": jnp.array([first, 9, 2], jnp.int32),
             "active": jnp.array([True, False, True]), **extra}
    got = jax.jit(lambda p, b, c: lm.prefill_chunk(cfg, p, b, c))(
        params, batch, cache)
    want = jax.jit(lambda p, b, c: prefill_per_layer(cfg, p, b, c))(
        params, batch, cache)
    _same(got, want)


def test_dense_decode_writes_only_its_rows(monkeypatch):
    """The in-place route changes one row per active slot and layer of
    the stacked cache, nothing else."""
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    cfg, params, cache, _ = _setup("smollm-360m", False)
    pos = jnp.array([MAX_SEQ - 1, 5, 11], jnp.int32)
    batch = {"tokens": _tokens(cfg, 1), "pos": pos,
             "active": jnp.array([True, False, True])}
    _, new = jax.jit(lambda p, b, c: lm.decode_step(cfg, p, b, c))(
        params, batch, cache)
    for name in ("k", "v"):
        old, upd = np.asarray(cache[0][name]), np.asarray(new[0][name])
        changed = np.any(old != upd, axis=(1, 4))       # [L, B, S]
        want = np.zeros_like(changed)
        want[:, 0, MAX_SEQ - 1] = want[:, 2, 11] = True
        np.testing.assert_array_equal(changed, want)
