"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy:

* backend == 'tpu'      -> compiled Pallas kernel (BlockSpec VMEM tiling)
* REPRO_PALLAS=interpret -> Pallas kernel body interpreted on CPU (tests)
* otherwise             -> pure-jnp reference (XLA), the kernels' oracle

so models always call ``ops.flash_attention`` / ``ops.ssd_scan`` and get the
kernel on a TPU and its reference elsewhere.  Pallas kernels have no
reverse-mode rule, so the differentiable entry points (``flash_attention``,
``ssd_scan``) run the kernel forward and differentiate the reference in the
backward pass (``_kernel_with_ref_vjp``).  XLA cannot partition a Mosaic
call, so under sharding rules every kernel runs per shard inside
``shard_map`` (``_per_shard``).

Tuned-config plumbing (``repro.tune``): every block/chunk knob defaults
to ``None``, meaning "consult the persistent best-config cache for this
(kernel, shape bucket, dtype, backend), else use the built-in default".
A cache hit dispatches with the tuned blocks; a miss — including no
cache file at all — is byte-identical to the pre-tuning behavior.  An
explicit argument always wins over the cache.  Tuned values are
re-validated against the kernels' divisibility constraints here, so a
stale or foreign cache entry degrades to the default instead of
crashing the caller.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.sharding.rules import current_rules
from repro.tune import cache as _tune_cache

# built-in defaults served on a cache miss — mirrored by
# repro.tune.space.SPECS[*].defaults (the tuner's incumbents)
_DEFAULT_BLOCK_Q = 128
_DEFAULT_BLOCK_K = 128
_DEFAULT_DECODE_BLOCK_K = 512
_DEFAULT_CHUNK = 64


def _mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env in ("interpret", "ref", "naive", "kernel"):
        return env
    if jax.default_backend() == "tpu":
        return "kernel"
    return "ref"


def _kernel_with_ref_vjp(kernel_fn, ref_fn, *args, bwd_scope: str):
    """``kernel_fn(*args)``, with the gradient of ``ref_fn`` (same math,
    plain XLA) as its VJP — the backward recomputes the reference, under
    the ``jax.named_scope`` ``bwd_scope``."""
    @jax.custom_vjp
    def f(*a):
        return kernel_fn(*a)

    def fwd(*a):
        return kernel_fn(*a), a

    def bwd(a, g):
        with jax.named_scope(bwd_scope):
            return jax.vjp(ref_fn, *a)[1](g)

    f.defvjp(fwd, bwd)
    return f(*args)


def _per_shard(fn, args, in_logical, out_logical):
    """``fn(*args)`` for a Pallas kernel under the active sharding rules.

    With no rules installed this is a plain call.  On a mesh the kernel
    runs inside ``shard_map``, each device on its block of the axes the
    rules split.  ``in_logical`` holds one logical spec per argument
    (``None`` arguments are passed through); ``out_logical`` one spec,
    or a list with one per output."""
    rules = current_rules()
    if rules is None:
        return fn(*args)
    live = [i for i, a in enumerate(args) if a is not None]

    def local(*xs):
        full = list(args)
        for i, x in zip(live, xs, strict=True):
            full[i] = x
        return fn(*full)

    xs = [args[i] for i in live]
    in_specs = tuple(rules.spec(in_logical[i], args[i].shape) for i in live)
    out = jax.eval_shape(local, *xs)
    out_specs = (tuple(rules.spec(lg, o.shape)
                       for lg, o in zip(out_logical, out, strict=True))
                 if isinstance(out_logical, list)
                 else rules.spec(out_logical, out.shape))
    return jax.shard_map(local, mesh=rules.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*xs)


def _head_axis(H: int, K: int) -> str | None:
    """Logical name for the head axes of a GQA kernel's operands: split
    over the rules' head axis only when both H and K divide, so every
    shard keeps whole query groups with their KV head."""
    rules = current_rules()
    if rules is None:
        return None
    n = rules.axis_size(rules.table.get("heads", ()))
    return "heads" if H % n == 0 and K % n == 0 else None


def _tuned(kernel: str, shape: dict, dtype) -> dict:
    """Best-config cache lookup for the current dispatch backend
    (empty dict on any miss)."""
    return _tune_cache.best_config(kernel, shape, str(dtype)) or {}


def _fit_block(value, dim: int, default: int) -> int:
    """Accept a tuned block size only if it satisfies the kernel's
    static constraint after the kernel's own min-clamp; otherwise fall
    back to the default (preserving the exact pre-tuning behavior,
    including its failure modes)."""
    v = int(value)
    clamped = min(v, dim)
    if clamped > 0 and dim % clamped == 0:
        return v
    return default


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, block_q: int | None = None,
                    block_k: int | None = None):
    """GQA flash attention. q: [B,Sq,H,D], k/v: [B,Sk,K,D] -> [B,Sq,H,D].

    ``block_q``/``block_k``: explicit value > tuned cache > 128."""
    mode = _mode()
    if mode == "naive":
        return ref.attention_ref(q, k, v, causal=causal, scale=scale,
                                 q_offset=q_offset)
    Bsz, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if block_q is None or block_k is None:
        cfg = _tuned("flash_attention",
                     {"b": Bsz, "s": Sk, "h": H, "kvh": K, "d": D}, q.dtype)
        if block_q is None:
            block_q = _fit_block(cfg.get("block_q", _DEFAULT_BLOCK_Q),
                                 Sq, _DEFAULT_BLOCK_Q)
        if block_k is None:
            block_k = _fit_block(cfg.get("block_k", _DEFAULT_BLOCK_K),
                                 Sk, _DEFAULT_BLOCK_K)
    # blockwise (flash-style) XLA lowering — same algorithm as the
    # Pallas kernel, honest HBM profile on non-TPU backends.
    # (custom_vjp: positional args only)
    from repro.kernels.xla_flash import blockwise_attention

    def xla(q, k, v):
        return blockwise_attention(q, k, v, causal, scale, q_offset,
                                   max(block_k, 512))

    if mode == "ref":
        return xla(q, k, v)
    from repro.kernels import flash_attention as fk

    def call(q, k, v):
        return fk.flash_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
            interpret=(mode == "interpret"))

    spec = ("batch", None, _head_axis(H, K), None)

    def kernel(q, k, v):
        return _per_shard(call, (q, k, v), (spec,) * 3, spec)

    return _kernel_with_ref_vjp(kernel, xla, q, k, v,
                                bwd_scope="attention_bwd")


def _decode_block_k(sk: int) -> int:
    """The default decode block: the largest of 512, 256 and 128 rows that
    divides ``sk``, else 128 (the wrapper pads).  The kernel streams each
    live block of the cache from HBM, and fewer, larger blocks keep the
    DMA busy; one that divides spares the wrapper its pad, a copy of the
    layer."""
    return next((b for b in (_DEFAULT_DECODE_BLOCK_K, 256) if sk % b == 0),
                128)


def decode_attention(q, k, v, kv_len, layer=0, *,
                     scale: float | None = None, block_k: int | None = None):
    """Sq=1 GQA decode attention over a ragged KV cache.

    q: [B,H,D], k/v: [K,B,Sk,D/Dv] (the kv-head-major cache layout) or the
    stacked [L,K,B,Sk,D/Dv] of every layer, kv_len: [B] int32, layer:
    int32 scalar, the layer of a stacked k/v to read -> [B,H,Dv].  Same
    dispatch policy as ``flash_attention``: the pure-jnp reference on
    non-TPU backends, the Pallas decode kernel
    (``kernels/decode_attention.py``) runs on TPU or under
    ``REPRO_PALLAS=interpret``.  ``block_k``: explicit > tuned >
    ``_decode_block_k`` (the wrapper zero-pads Sk, so any positive tuned
    value is valid)."""
    mode = _mode()
    if mode in ("ref", "naive"):
        return ref.decode_attention_ref(q, k, v, kv_len, layer, scale=scale)
    Bsz, H, D = q.shape
    K, Sk = k.shape[-4], k.shape[-2]
    if block_k is None:
        cfg = _tuned("decode_attention",
                     {"b": Bsz, "sk": Sk, "h": H, "kvh": K, "d": D},
                     q.dtype)
        block_k = int(cfg.get("block_k", 0))
        if block_k <= 0:
            block_k = _decode_block_k(Sk)
    from repro.kernels import decode_attention as dk

    def call(q, k, v, kv_len, layer):
        return dk.decode_attention(q, k, v, kv_len, layer, scale=scale,
                                   block_k=block_k,
                                   interpret=(mode == "interpret"))

    # split heads the way the cache is stored, so it is never gathered
    h = _head_axis(H, K)
    cache = (None,) * (k.ndim - 4) + (h, "batch", None, None)
    return _per_shard(call, (q, k, v, kv_len, jnp.asarray(layer, jnp.int32)),
                      (("batch", h, None), cache, cache, ("batch",), ()),
                      ("batch", h, None))


def decode_attention_paged(q, k_pool, v_pool, page_table, kv_len, *,
                           scale: float | None = None):
    """Sq=1 GQA decode attention against a paged KV pool.

    q: [B,H,D], k_pool/v_pool: [K,P,ps,D/Dv], page_table: [B,W] int32,
    kv_len: [B] int32 -> [B,H,Dv].  Same dispatch policy as
    ``decode_attention``: the pure-jnp reference (page gather + ragged
    dense attention) on non-TPU backends, the page-table Pallas kernel
    (scalar-prefetched tables steering the K/V DMA) on TPU or under
    ``REPRO_PALLAS=interpret``.  The page geometry is fixed by the pool
    the caller built — the tuned ``page_size`` recommendation is
    consumed where the pool is constructed (``serve/engine.py``)."""
    mode = _mode()
    if mode in ("ref", "naive"):
        return ref.decode_attention_paged_ref(q, k_pool, v_pool, page_table,
                                              kv_len, scale=scale)
    from repro.kernels import decode_attention as dk

    def call(q, k_pool, v_pool, page_table, kv_len):
        return dk.decode_attention_paged(q, k_pool, v_pool, page_table,
                                         kv_len, scale=scale,
                                         interpret=(mode == "interpret"))

    h = _head_axis(q.shape[1], k_pool.shape[0])
    pool = (h, None, None, None)    # a slot's pages may sit anywhere
    return _per_shard(call, (q, k_pool, v_pool, page_table, kv_len),
                      (("batch", h, None), pool, pool, ("batch", None),
                       ("batch",)),
                      ("batch", h, None))


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int | None = None, h0=None,
             return_final_state: bool = False):
    """Mamba-2 SSD chunked scan. See kernels.ref.ssd_chunked_ref.

    ``chunk``: explicit value > tuned cache > 64.  Model code that bakes
    a semantic chunk into its config keeps passing it explicitly (and is
    byte-identical); pass ``None`` to opt into tuned chunking."""
    if chunk is None:
        Bsz, S, H, P = x.shape
        G, N = Bm.shape[2], Bm.shape[3]
        cfg = _tuned("ssd_scan",
                     {"b": Bsz, "s": S, "h": H, "p": P, "g": G, "n": N},
                     x.dtype)
        chunk = _fit_block(cfg.get("chunk", _DEFAULT_CHUNK), S,
                           _DEFAULT_CHUNK)
    mode = _mode()

    def reference(x, dt, A, Bm, Cm, h0):
        return ref.ssd_chunked_ref(
            x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
            return_final_state=return_final_state)

    if mode == "ref":
        return reference(x, dt, A, Bm, Cm, h0)
    from repro.kernels import ssd_scan as sk

    def call(x, dt, A, Bm, Cm, h0):
        return sk.ssd_scan(
            x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
            return_final_state=return_final_state,
            interpret=(mode == "interpret"))

    rows = ("batch", None, None, None)
    out = [rows, rows] if return_final_state else rows

    def kernel(x, dt, A, Bm, Cm, h0):
        return _per_shard(call, (x, dt, A, Bm, Cm, h0),
                          (rows, ("batch", None, None), (None,), rows, rows,
                           rows), out)

    return _kernel_with_ref_vjp(kernel, reference, x, dt, A, Bm, Cm, h0,
                                bwd_scope="ssd_bwd")
