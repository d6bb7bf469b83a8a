"""Sq=1 decode-attention Pallas kernel vs the pure-jnp reference.

Runs the kernel in interpret mode (CPU CI); covers GQA group ratios,
ragged per-slot kv lengths and Sk that does not divide block_k (the
wrapper zero-pads and the in-kernel mask must keep the tail dead).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.ref import (decode_attention_paged_ref,
                               decode_attention_ref, gather_kv_pages)


def _inputs(B, Sk, H, K, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (K, B, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (K, B, Sk, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("H,K", [(4, 4), (8, 2), (8, 1)])
def test_gqa_ratios(H, K):
    B, Sk, D = 2, 64, 32
    q, k, v = _inputs(B, Sk, H, K, D)
    kv_len = jnp.array([Sk, Sk], jnp.int32)
    got = decode_attention(q, k, v, kv_len, block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ragged_kv_len_masks_cache_tail():
    B, Sk, H, K, D = 4, 96, 8, 2, 32
    q, k, v = _inputs(B, Sk, H, K, D, seed=1)
    kv_len = jnp.array([1, 17, 32, 96], jnp.int32)
    got = decode_attention(q, k, v, kv_len, block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # tail beyond kv_len must not influence the output at all
    k2 = k.at[:, :, 40:].set(1e4)
    v2 = v.at[:, :, 40:].set(-1e4)
    got2 = decode_attention(q[:2], k2[:, :2], v2[:, :2], kv_len[:2],
                            block_k=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(got[:2]))


@pytest.mark.parametrize("Sk,block_k", [(7, 4), (100, 32), (130, 128)])
def test_non_dividing_sk(Sk, block_k):
    B, H, K, D = 2, 4, 2, 16
    q, k, v = _inputs(B, Sk, H, K, D, seed=2)
    kv_len = jnp.array([Sk, max(1, Sk // 3)], jnp.int32)
    got = decode_attention(q, k, v, kv_len, block_k=block_k, interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_scale_override_and_vdim():
    B, Sk, H, K, D = 2, 32, 4, 2, 16
    q, k, v = _inputs(B, Sk, H, K, D, seed=3)
    kv_len = jnp.array([5, 32], jnp.int32)
    got = decode_attention(q, k, v, kv_len, scale=0.25, block_k=16,
                           interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,K,Sk,block_k,lens", [
    (8, 2, 64, 32, (1, 64, 30)),      # GQA groups of 4, ragged to 1 and Sk
    (4, 4, 40, 16, (40, 1, 17)),      # no groups; Sk a block multiple + 8
    (8, 1, 100, 32, (100, 3, 64)),    # one KV head; non-dividing Sk
])
def test_stacked_layer_matches_its_slice(H, K, Sk, block_k, lens):
    """A stacked [L, K, B, Sk, D] cache read at layer l, through the
    kernel's index maps, gives what the layer's own slice gives, bit for
    bit, in the kernel and in the reference."""
    L, B, D = 3, len(lens), 16
    q, k, v = _inputs(B, L * Sk, H, K, D, seed=5)
    k = k.reshape(K, B, L, Sk, D).transpose(2, 0, 1, 3, 4)
    v = v.reshape(K, B, L, Sk, D).transpose(2, 0, 1, 3, 4)
    kv_len = jnp.array(lens, jnp.int32)
    kern = jax.jit(lambda *a: decode_attention(*a, block_k=block_k,
                                               interpret=True))
    for layer in range(L):
        l = jnp.int32(layer)
        got = kern(q, k, v, kv_len, l)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(kern(q, k[layer], v[layer], kv_len)))
        ref = decode_attention_ref(q, k, v, kv_len, l)
        np.testing.assert_array_equal(
            np.asarray(ref),
            np.asarray(decode_attention_ref(q, k[layer], v[layer], kv_len)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("stacked", [False, True])
def test_ops_dispatch_ref_matches_kernel(stacked, monkeypatch):
    from repro.kernels import ops
    B, Sk, H, K, D = 2, 48, 4, 2, 16
    q, k, v = _inputs(B, Sk, H, K, D, seed=4)
    kv_len = jnp.array([9, 48], jnp.int32)
    layer = ()
    if stacked:     # the layer of interest between two others
        k = jnp.stack([k[::-1], k, -k])
        v = jnp.stack([-v, v, v[::-1]])
        layer = (jnp.int32(1),)
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    via_ref = ops.decode_attention(q, k, v, kv_len, *layer)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    via_kernel = ops.decode_attention(q, k, v, kv_len, *layer)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged variant: K/V live in a [K, P, ps, D] pool, steered by page tables
# ---------------------------------------------------------------------------
def _paged_inputs(B, W, ps, H, K, D, num_pages, seed=0):
    """Pool + *shuffled* page tables: each slot's pages are scattered over
    the pool so physical contiguity can't mask indexing bugs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], (K, num_pages, ps, D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (K, num_pages, ps, D), jnp.float32)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_pages)[:B * W]
    table = jnp.asarray(perm.reshape(B, W).astype(np.int32))
    return q, k_pool, v_pool, table


@pytest.mark.parametrize("H,K", [(4, 4), (8, 2), (8, 1)])
def test_paged_matches_paged_ref(H, K):
    B, W, ps, D = 2, 4, 8, 32
    q, kp, vp, pt = _paged_inputs(B, W, ps, H, K, D, num_pages=16)
    kv_len = jnp.array([W * ps, 11], jnp.int32)   # full + partial last page
    got = decode_attention_paged(q, kp, vp, pt, kv_len, interpret=True)
    ref = decode_attention_paged_ref(q, kp, vp, pt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_matches_dense_on_gathered_layout():
    """The paged kernel over a shuffled table must equal the dense ref over
    the gathered [K, B, W*ps, D] view — same math, different addressing."""
    B, W, ps, H, K, D = 3, 5, 4, 8, 2, 16
    q, kp, vp, pt = _paged_inputs(B, W, ps, H, K, D, num_pages=32, seed=1)
    kv_len = jnp.array([1, 7, 20], jnp.int32)
    got = decode_attention_paged(q, kp, vp, pt, kv_len, interpret=True)
    ref = decode_attention_ref(q, gather_kv_pages(kp, pt),
                               gather_kv_pages(vp, pt), kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_masks_unwritten_page_tail():
    """Rows past kv_len — the unfilled tail of the last page and whole
    unread pages — must not influence the output, even when poisoned."""
    B, W, ps, H, K, D = 2, 4, 8, 4, 2, 16
    q, kp, vp, pt = _paged_inputs(B, W, ps, H, K, D, num_pages=16, seed=2)
    kv_len = jnp.array([5, 13], jnp.int32)
    base = decode_attention_paged(q, kp, vp, pt, kv_len, interpret=True)
    # poison every row of every page, then restore only the live prefixes
    kp2, vp2 = kp, vp
    for b in range(B):
        live = int(kv_len[b])
        for j in range(W):
            lo, hi = j * ps, min((j + 1) * ps, live)
            pg = int(pt[b, j])
            n = max(0, hi - lo)
            keep_k = kp[:, pg, :n]
            keep_v = vp[:, pg, :n]
            kp2 = kp2.at[:, pg].set(1e4).at[:, pg, :n].set(keep_k)
            vp2 = vp2.at[:, pg].set(-1e4).at[:, pg, :n].set(keep_v)
    got = decode_attention_paged(q, kp2, vp2, pt, kv_len, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_paged_sentinel_table_entries_are_safe():
    """Unmapped table entries hold the out-of-range sentinel num_pages;
    both kernel and ref must clamp (not NaN-fill) since those rows sit
    beyond kv_len anyway."""
    B, W, ps, H, K, D = 2, 4, 4, 4, 2, 16
    q, kp, vp, pt = _paged_inputs(B, W, ps, H, K, D, num_pages=8, seed=3)
    kv_len = jnp.array([3, 6], jnp.int32)
    pt = pt.at[0, 1:].set(8).at[1, 2:].set(8)      # sentinel == num_pages
    got = decode_attention_paged(q, kp, vp, pt, kv_len, interpret=True)
    ref = decode_attention_paged_ref(q, kp, vp, pt, kv_len)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ops_dispatch_paged(monkeypatch):
    from repro.kernels import ops
    B, W, ps, H, K, D = 2, 3, 8, 4, 2, 16
    q, kp, vp, pt = _paged_inputs(B, W, ps, H, K, D, num_pages=8, seed=4)
    kv_len = jnp.array([9, 24], jnp.int32)
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    via_ref = ops.decode_attention_paged(q, kp, vp, pt, kv_len)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    via_kernel = ops.decode_attention_paged(q, kp, vp, pt, kv_len)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_ref),
                               atol=2e-5, rtol=2e-5)
