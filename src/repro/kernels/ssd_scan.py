"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

TPU-native design:
  * grid = (batch, heads, S/chunk); the chunk axis is the innermost
    'arbitrary' dimension and the running SSM state h [P, N] lives in VMEM
    scratch across chunk steps — the sequential inter-chunk recurrence maps
    onto the TPU grid-carry idiom instead of a GPU block-parallel scan.
  * per-chunk work is two MXU matmuls (C·Bᵀ intra-chunk quadratic term and
    the state in/out projections) over [L, N]x[N, L] / [L, N]x[N, P] blocks;
    L=chunk and N, P are 64–128 so everything is MXU-shaped.
  * B/C group mapping (GQA-style G groups) happens in the index_map
    (h // heads_per_group), no replication materialised.
  * the kernel reads head-major operands (x [B, H, S, P], B/C
    [B, G, S, N]; the wrapper transposes), so every block ends in
    (chunk, P|N) as Mosaic's (8, 128) tiling needs.  dt arrives as one
    [1, chunk] row per grid step, picked out of an (8, chunk) block of
    [B, H, S/chunk, chunk] by a masked sublane sum; A is read from SMEM.
    The column forms the recurrence also needs are derived in-kernel with
    masked reductions (``_flip``) — exact fp32, no
    transposes and no 1-D values, which Mosaic handles poorly.
  * fp32 state and decay math in-kernel (mixed_precision_sensitive:
    cumsum + exp), inputs/outputs in the model dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DT_ROWS = 8   # chunks of dt per block: one sublane tile


def _flip(v):
    """[1, L] <-> [L, 1]: the diagonal of ``v`` broadcast to [L, L],
    summed along the other axis."""
    L = max(v.shape)
    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    diag = jnp.where(li == si, jnp.broadcast_to(v, (L, L)), 0.0)
    return jnp.sum(diag, axis=1 if v.shape[0] == 1 else 0, keepdims=True)


def _kernel(A_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref,
            h_scr, *, chunk: int, has_h0: bool):
    h = pl.program_id(1)
    ic = pl.program_id(2)
    nc = pl.num_programs(2)
    L = chunk

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = (h0_ref[0, 0].astype(jnp.float32) if has_h0
                      else jnp.zeros_like(h_scr))

    x = x_ref[0, 0].astype(jnp.float32)             # [L, P]
    rows = dt_ref[0, 0].astype(jnp.float32)         # [8, L]
    ri = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    dt = jnp.sum(jnp.where(ri == ic % _DT_ROWS, rows, 0.0), axis=0,
                 keepdims=True)                     # [1, L]
    A = A_ref[h]                                    # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)            # [L, N]
    Cm = c_ref[0, 0].astype(jnp.float32)            # [L, N]

    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    dA = dt * A                                     # [1, L]
    # cum_l = sum_{s <= l} dA_s, as a column and as a row
    cum = jnp.sum(jnp.where(si <= li, jnp.broadcast_to(dA, (L, L)), 0.0),
                  axis=1, keepdims=True)            # [L, 1]
    cum_row = _flip(cum)                            # [1, L]
    cum_last = cum_row[:, L - 1:L]                  # [1, 1]
    # intra-chunk: scores[l, s] = C_l·B_s · exp(cum_l - cum_s) · dt_s, s<=l
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [L, L]
    decay = jnp.exp(cum - cum_row)
    scores = jnp.where(li >= si, cb * decay * dt, 0.0)
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [L, P]

    # inter-chunk: y += exp(cum_l) * C_l · h_in   (h: [P, N])
    h_in = h_scr[...]
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cm, h_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h_out = exp(cum_L) h_in + sum_s exp(cum_L - cum_s) dt_s x_s B_sᵀ
    w = jnp.exp(cum_last - cum) * _flip(dt)         # [L, 1]
    state_in = jax.lax.dot_general(
        x * w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [P, N]
    h_scr[...] = jnp.exp(cum_last) * h_in + state_in

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _write_state():
        hT_ref[0, 0] = h_scr[...]


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64, h0=None,
             return_final_state: bool = False, interpret: bool = False):
    """x: [B,S,H,P], dt: [B,S,H], A: [H], Bm/Cm: [B,S,G,N].

    Returns y [B,S,H,P] (and final state [B,H,P,N] fp32 if requested)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0, (H, G)
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    has_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    # dt as [B, H, nc, chunk] rows, padded to whole (8, chunk) blocks
    dt_rows = jnp.swapaxes(dt.astype(jnp.float32), 1, 2).reshape(
        Bsz, H, nc, chunk)
    dt_rows = jnp.pad(dt_rows, ((0, 0), (0, 0), (0, -nc % _DT_ROWS), (0, 0)))
    head_major = functools.partial(jnp.swapaxes, axis1=1, axis2=2)

    kernel = functools.partial(_kernel, chunk=chunk, has_h0=has_h0)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, _DT_ROWS, chunk),
                         lambda b, h, c: (b, h, c // _DT_ROWS, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A.astype(jnp.float32), head_major(x), dt_rows, head_major(Bm),
      head_major(Cm), h0)
    y = head_major(y)
    if return_final_state:
        return y, hT
    return y
