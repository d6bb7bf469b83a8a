"""Pure-jnp oracles for every Pallas kernel.

These are the *reference* implementations:
  * used as the compute path on non-TPU backends (this container),
  * used as the allclose oracle for the Pallas kernels (interpret=True),
  * written for clarity and numerical robustness (fp32 softmax/state).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, K, D]
    v: jax.Array,  # [B, Sk, K, D]
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Grouped-query attention, fp32 softmax. Returns [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Sq, K, G, D).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,       # [B, H, D]
    k: jax.Array,       # [K, B, Sk, D], or stacked [L, K, B, Sk, D]
    v: jax.Array,       # [K, B, Sk, Dv], or stacked [L, K, B, Sk, Dv]
    kv_len: jax.Array,  # [B] int32 — position p attended iff p < kv_len
    layer=0,            # int32 scalar: the layer of a stacked k/v
    *,
    scale: float | None = None,
) -> jax.Array:
    """Single-token (Sq=1) GQA decode attention over a ragged KV cache.

    K/V are in the decode cache's kv-head-major layout.  fp32 softmax;
    matches ``kernels/decode_attention.py``.  Every slot must have
    ``kv_len >= 1`` (an all-masked row would softmax to NaN).
    Returns [B, H, Dv]."""
    if k.ndim == 5:
        k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
    B, H, D = q.shape
    K, Sk = k.shape[0], k.shape[2]
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, K, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,kbsd->bkgs", qg, k.astype(jnp.float32)) * scale
    mask = jnp.arange(Sk)[None, :] < kv_len[:, None]          # [B, Sk]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,kbsd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, v.shape[-1]).astype(q.dtype)


def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialise a slot-major view of a paged pool.

    pool: [num_pages, page_size, ...]; page_table: [B, W] int32 (physical
    page backing each slot's logical page).  Returns [B, W*page_size, ...]
    where row ``j`` of slot ``b`` is token position ``j`` — the dense
    layout the non-paged reference kernels expect.  Rows past a slot's
    live length are stale pool contents; callers mask them by kv_len.
    Unmapped table entries hold an out-of-range sentinel — clamp instead
    of jnp.take's default NaN fill (0 * NaN would poison the masked
    matmul rows)."""
    B, W = page_table.shape
    pt = jnp.minimum(page_table, pool.shape[0] - 1)
    g = jnp.take(pool, pt, axis=0)                  # [B, W, ps, ...]
    return g.reshape(B, W * pool.shape[1], *pool.shape[2:])


def gather_kv_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """``gather_pages`` per KV head: [K, P, ps, D] -> [K, B, W*ps, D], the
    dense decode cache's layout."""
    return jax.vmap(gather_pages, in_axes=(0, None))(pool, page_table)


def decode_attention_paged_ref(
    q: jax.Array,           # [B, H, D]
    k_pool: jax.Array,      # [K, P, ps, D]
    v_pool: jax.Array,      # [K, P, ps, Dv]
    page_table: jax.Array,  # [B, W] int32
    kv_len: jax.Array,      # [B] int32
    *,
    scale: float | None = None,
) -> jax.Array:
    """Paged Sq=1 decode attention: gather the slot's pages into a dense
    [K, B, W*ps, ...] view, then run the ragged dense reference.  Matches
    ``kernels/decode_attention.py::decode_attention_paged``.
    Returns [B, H, Dv]."""
    k = gather_kv_pages(k_pool, page_table)
    v = gather_kv_pages(v_pool, page_table)
    return decode_attention_ref(q, k, v, kv_len, scale=scale)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) — chunked reference
# ---------------------------------------------------------------------------
def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < t <= i} x[..., t].

    x: [..., L] -> [..., L, L] lower-triangular cumulative sums.
    """
    L = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    out = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked_ref(
    x: jax.Array,   # [B, S, H, P]
    dt: jax.Array,  # [B, S, H]   (already softplus'ed, > 0)
    A: jax.Array,   # [H]         (negative)
    Bm: jax.Array,  # [B, S, G, N]
    Cm: jax.Array,  # [B, S, G, N]
    *,
    chunk: int = 64,
    h0: jax.Array | None = None,  # [B, H, P, N] initial state
    return_final_state: bool = False,
):
    """Chunked SSD: y_t = C_t · h_t,  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    Heads H are grouped into G B/C groups (H % G == 0).
    Computation in fp32; output cast back to x.dtype.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        # dt=0 padding is state-neutral (decay 1, zero input contribution)
        zpad = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = zpad(x), zpad(dt), zpad(Bm), zpad(Cm)
        S_orig, S = S, S + pad
    nc = S // chunk
    rep = H // G

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
    Bh = jnp.repeat(Bf, rep, axis=3)  # [B, nc, L, H, N]
    Ch = jnp.repeat(Cf, rep, axis=3)

    dA = dtf * A[None, None, None, :]              # [B, nc, L, H]
    dAc = jnp.cumsum(dA, axis=2)                   # within-chunk cumsum
    # --- intra-chunk (quadratic within chunk) ---
    Lmat = jnp.exp(_segsum(jnp.swapaxes(dA, 2, 3)))  # [B, nc, H, L, L]
    scores = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh) * Lmat
    scores = scores * dtf.transpose(0, 1, 3, 2)[:, :, :, None, :]  # dt_s
    y_intra = jnp.einsum("bchls,bcshp->bclhp", scores, xf)

    # --- chunk states ---
    decay_to_end = jnp.exp(dAc[:, :, -1:, :] - dAc)          # [B, nc, L, H]
    Sc = jnp.einsum(
        "bclhn,bclh,bclhp->bchnp", Bh, decay_to_end * dtf, xf
    )  # [B, nc, H, N, P]

    # --- inter-chunk recurrence over nc chunks ---
    chunk_decay = jnp.exp(dAc[:, :, -1, :])  # [B, nc, H]
    h0 = (jnp.zeros((Bsz, H, N, P), jnp.float32) if h0 is None
          else jnp.swapaxes(h0.astype(jnp.float32), -1, -2))  # ->[B,H,N,P]

    def step(h, inp):
        dec, s = inp  # dec [B,H], s [B,H,N,P]
        h_new = h * dec[..., None, None] + s
        return h_new, h

    hT, h_prev = jax.lax.scan(
        step,
        h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(Sc, 1, 0)),
        unroll=nc if os.environ.get("REPRO_UNROLL_INNER") else 1,
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # [B, nc, H, N, P] state entering chunk
    y_inter = jnp.einsum(
        "bclhn,bchnp->bclhp", Ch * jnp.exp(dAc)[..., None], h_prev
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    if pad:
        y = y[:, :S_orig]
    if return_final_state:
        return y.astype(x.dtype), jnp.swapaxes(hT, -1, -2)  # [B,H,P,N]
    return y.astype(x.dtype)


def ssd_sequential_ref(x, dt, A, Bm, Cm, h0=None):
    """O(S) sequential oracle (the definition). Returns (y, h_final).

    h: [B, H, P, N];  y_t = einsum(C_t, h_t)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bh = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)
    Ch = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(h, t):
        dA = jnp.exp(dtf[:, t] * A[None, :])  # [B, H]
        dBx = jnp.einsum("bh,bhn,bhp->bhpn", dtf[:, t], Bh[:, t], xf[:, t])
        h = h * dA[..., None, None] + dBx
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t])
        return h, y

    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1)  # [B, S, H, P]
    return y.astype(x.dtype), hT


def ssd_decode_step_ref(x, dt, A, Bm, Cm, h):
    """Single-token SSD update. x: [B,H,P], dt: [B,H], Bm/Cm: [B,G,N],
    h: [B,H,P,N] -> (y [B,H,P], h')."""
    G = Bm.shape[1]
    H = x.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)
    Ch = jnp.repeat(Cm.astype(jnp.float32), rep, axis=1)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A[None, :])
    h_new = h * dA[..., None, None] + jnp.einsum("bh,bhn,bhp->bhpn", dtf, Bh, xf)
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Ch)
    return y.astype(x.dtype), h_new
