"""Measurement tasks: compile + time one kernel config.

One module-level ``@task`` function per kernel (module-level so
``FunctionTask`` pickles by reference and the sweep can run on the
LocalEngine's worker processes).  Each task:

* statically re-validates the config (``space.valid``) and raises
  ``ValueError`` *before* building any inputs — a config that slipped
  past the grid filter is rejected loudly instead of tripping a kernel
  assert deep inside a client;
* builds seeded inputs for the cell's shape, then times the call
  **through ``kernels/ops.py`` dispatch** (never bypassing it — the
  measurement exercises exactly the code path a model would hit on this
  backend, Pallas kernel / interpret / XLA reference alike);
* warms up exactly once and takes repeated outlier-rejected samples
  (:func:`repro.tune.measure.time_fn`) — virtualised-hardware timing
  noise is rejected, not averaged in.

Every task declares ``hardness`` and ``sim_duration`` from the roofline
predicted cost (``repro.tune.space``), which is what lets the sweep run
through ``Experiment(engine="sim")`` with the paper's timeout/domino
pruning fully active: a config whose *predicted* virtual runtime blows
the timeout is killed and domino-prunes everything predicted harder,
without the host ever paying for the measurement.

Returns ``(runtime_us, n_kept, n_samples)`` per config.
"""
from __future__ import annotations

import functools

from repro.core.space import task
from repro.tune import space as _space
from repro.tune.measure import time_fn

RESULT_TITLES = ("runtime_us", "n_kept", "n_samples")
MEASURE_ITERS = 5


def _check(kernel: str, cell: dict) -> None:
    if not _space.valid(kernel, cell):
        raise ValueError(
            f"invalid {kernel} config {cell!r}: violates the kernel's "
            f"divisibility constraints (should have been filtered "
            f"statically by repro.tune.space.build_space)")


def _keys(*ks):
    import jax

    return jax.random.split(jax.random.PRNGKey(0), len(ks))


def _normal(key, shape, dtype):
    import jax

    return jax.random.normal(key, shape, dtype)


def _hard(kernel):
    def h(**cell):
        return _space.hardness_of(kernel, cell)
    return h


def _simdur(kernel):
    def s(**cell):
        return _space.sim_duration_s(kernel, cell)
    return s


def _timed(fn, *args):
    mean_us, kept, samples = time_fn(fn, *args, iters=MEASURE_ITERS)
    return mean_us, kept, len(samples)


@task(result_titles=RESULT_TITLES, hardness=_hard("flash_attention"),
      sim_duration=_simdur("flash_attention"))
def measure_flash_attention(b, s, h, kvh, d, dtype, block_q, block_k):
    cell = dict(b=b, s=s, h=h, kvh=kvh, d=d, dtype=dtype,
                block_q=block_q, block_k=block_k)
    _check("flash_attention", cell)
    import jax.numpy as jnp

    from repro.kernels import ops

    dt = jnp.dtype(dtype)
    kq, kk, kv = _keys("q", "k", "v")
    q = _normal(kq, (b, s, h, d), dt)
    k = _normal(kk, (b, s, kvh, d), dt)
    v = _normal(kv, (b, s, kvh, d), dt)
    fn = functools.partial(ops.flash_attention, causal=True,
                           block_q=block_q, block_k=block_k)
    return _timed(fn, q, k, v)


@task(result_titles=RESULT_TITLES, hardness=_hard("ssd_scan"),
      sim_duration=_simdur("ssd_scan"))
def measure_ssd_scan(b, s, h, p, g, n, dtype, chunk):
    cell = dict(b=b, s=s, h=h, p=p, g=g, n=n, dtype=dtype, chunk=chunk)
    _check("ssd_scan", cell)
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    dt = jnp.dtype(dtype)
    kx, kt, ka, kb, kc = _keys("x", "t", "a", "b", "c")
    x = _normal(kx, (b, s, h, p), dt)
    dtv = jax.nn.softplus(_normal(kt, (b, s, h), jnp.float32)).astype(dt)
    A = -jnp.exp(_normal(ka, (h,), jnp.float32) * 0.3)
    Bm = _normal(kb, (b, s, g, n), dt)
    Cm = _normal(kc, (b, s, g, n), dt)
    fn = functools.partial(ops.ssd_scan, chunk=chunk)
    return _timed(fn, x, dtv, A, Bm, Cm)


@task(result_titles=RESULT_TITLES, hardness=_hard("decode_attention"),
      sim_duration=_simdur("decode_attention"))
def measure_decode_attention(b, sk, h, kvh, d, dtype, block_k):
    cell = dict(b=b, sk=sk, h=h, kvh=kvh, d=d, dtype=dtype,
                block_k=block_k)
    _check("decode_attention", cell)
    import jax.numpy as jnp

    from repro.kernels import ops

    dt = jnp.dtype(dtype)
    kq, kk, kv = _keys("q", "k", "v")
    q = _normal(kq, (b, h, d), dt)
    k = _normal(kk, (kvh, b, sk, d), dt)
    v = _normal(kv, (kvh, b, sk, d), dt)
    # ragged fill levels, the serving steady state (deterministic)
    kv_len = jnp.asarray([sk - (i * sk // (2 * b)) for i in range(b)],
                         jnp.int32)
    fn = functools.partial(ops.decode_attention, block_k=block_k)
    return _timed(fn, q, k, v, kv_len)


@task(result_titles=RESULT_TITLES,
      hardness=_hard("decode_attention_paged"),
      sim_duration=_simdur("decode_attention_paged"))
def measure_decode_attention_paged(b, sk, kvh, g, d, dtype, page_size):
    cell = dict(b=b, sk=sk, kvh=kvh, g=g, d=d, dtype=dtype,
                page_size=page_size)
    _check("decode_attention_paged", cell)
    import jax.numpy as jnp

    from repro.kernels import ops

    dt = jnp.dtype(dtype)
    w = -(-sk // page_size)                 # pages per slot
    n_pages = b * w
    kq, kk, kv = _keys("q", "k", "v")
    q = _normal(kq, (b, kvh * g, d), dt)
    k_pool = _normal(kk, (kvh, n_pages, page_size, d), dt)
    v_pool = _normal(kv, (kvh, n_pages, page_size, d), dt)
    # each slot owns a contiguous page run, shuffled per-slot order is
    # exercised by the serve tests — here geometry cost is the question
    page_table = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, w)
    kv_len = jnp.asarray([sk - (i * sk // (2 * b)) for i in range(b)],
                         jnp.int32)
    return _timed(ops.decode_attention_paged, q, k_pool, v_pool,
                  page_table, kv_len)


MEASURE_TASKS = {
    "flash_attention": measure_flash_attention,
    "ssd_scan": measure_ssd_scan,
    "decode_attention": measure_decode_attention,
    "decode_attention_paged": measure_decode_attention_paged,
}


def measure_cell(kernel: str, cell: dict):
    """Measure one fully-specified cell inline (the tuner's incumbent
    measurement) — same code path as the sweep tasks."""
    return MEASURE_TASKS[kernel].fn(**cell)


__all__ = ["MEASURE_TASKS", "measure_cell", "RESULT_TITLES",
           "MEASURE_ITERS", "measure_flash_attention", "measure_ssd_scan",
           "measure_decode_attention", "measure_decode_attention_paged"]
