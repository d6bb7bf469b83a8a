"""Chip smoke test: the serve and train paths, end to end, on a TPU.

    python chip_smoke.py               # one chip: kernels, serve, train
    python chip_smoke.py --four-chips  # 2x2 host: sharded train vs one chip

One process drives the chip(s); it starts no other.  Everything runs at
the published widths of smollm-360m (mamba2-130m for the SSD kernel) with
random weights from fixed seeds.  There is no CPU fallback and no
interpret mode: without a TPU, or with ``REPRO_PALLAS`` set to anything
but ``kernel``, the script exits non-zero before printing a result.  Any
phase that fails raises, so the exit code is non-zero and the last line
below is never printed.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-360m"
SSD_ARCH = "mamba2-130m"
SEED = 0
# serve phase
SLOTS, MAX_SEQ, PREFILL_CHUNK = 8, 2048, 128
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 16, (128, 1024), 32
# train phases
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2048, 2, 5
SHARDED_BATCH, SHARDED_STEPS = 4, 3
# sharded vs one chip: 8x the loss gap read on a v5e 2x2 (1.259e-04),
# under the 1e-3..1e-2 the loss moves from step to step
SHARDED_LOSS_ATOL = 1e-3
# |p_sharded - p_one_chip| / |p_one_chip - p_init| after those steps: a
# dropped update reads 1.0, half the ZeRO-1 shards dropped 0.71, a misplaced
# shard 1.41; a 4-host-device CPU rehearsal at reduced width read 0.12
SHARDED_UPDATE_RTOL = 0.5
# bf16 kernel outputs against the fp32 references (tests' bf16 bounds)
ATTN_TOL, SSD_TOL = 5e-2, 1e-1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_chip(count: int) -> dict:
    """The device record of the last line; raises unless JAX sees
    ``count`` TPU chips of a kind with published peaks and the kernel
    dispatch is the compiled Pallas path."""
    import jax

    from repro.kernels import ops
    from repro.launch.roofline import peaks

    pallas = os.environ.get("REPRO_PALLAS")
    if pallas not in (None, "kernel"):
        raise SystemExit(f"REPRO_PALLAS={pallas!r}: the smoke test runs the "
                         "compiled kernels only")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) != count:
        raise SystemExit(f"need {count} TPU chip(s), JAX found {len(devices)}")
    if ops._mode() != "kernel":
        raise SystemExit(f"kernel dispatch is {ops._mode()!r}, not 'kernel'")
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"peaks {peaks(dev.device_kind)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------
def _check_kernel(name, kernel, reference, args, tol):
    """Compile ``kernel``, check for the Mosaic call, compare its outputs
    with ``reference`` (fp32 matmuls) on the chip."""
    import jax
    import numpy as np

    compiled = jax.jit(kernel).lower(*args).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    got = jax.tree_util.tree_leaves(compiled(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.tree_util.tree_leaves(jax.jit(reference)(*args))
    err, ok = 0.0, True
    for g, w in zip(got, want, strict=True):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        err = max(err, float(np.max(np.abs(g - w))))
        ok &= bool(np.all(np.isfinite(g)))
        ok &= bool(np.all(np.abs(g - w) <= tol + tol * np.abs(w)))
    log(f"kernel {name}: tpu_custom_call={mosaic} max_abs_err={err:.3e} "
        f"(tol {tol} abs + {tol} rel) {'ok' if ok else 'FAIL'}")
    if not (mosaic and ok):
        raise RuntimeError(f"kernel {name} failed on the chip")


def kernel_phase() -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_paged)
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd_scan import ssd_scan

    cfg = get_config(ARCH)
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, dtype=bf, scale=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    S = MAX_SEQ
    q, k, v = normal((1, S, H, D)), normal((1, S, K, D)), normal((1, S, K, D))
    _check_kernel("flash_attention", functools.partial(flash_attention,
                                                       causal=True),
                  functools.partial(ref.attention_ref, causal=True),
                  (q, k, v), ATTN_TOL)

    B = SLOTS
    qd = normal((B, H, D))
    kc, vc = normal((K, B, S, D)), normal((K, B, S, D))
    # ragged fill levels, as continuous batching leaves them
    kv_len = jnp.asarray([S - i * (S // (2 * B)) for i in range(B)],
                         jnp.int32)
    _check_kernel("decode_attention", decode_attention,
                  ref.decode_attention_ref, (qd, kc, vc, kv_len), ATTN_TOL)

    ps, W = 16, S // 16
    kp, vp = normal((K, B * W, ps, D)), normal((K, B * W, ps, D))
    perm = jax.random.permutation(next(ks), B * W).astype(jnp.int32)
    table = perm.reshape(B, W)
    _check_kernel("decode_attention_paged", decode_attention_paged,
                  ref.decode_attention_paged_ref,
                  (qd, kp, vp, table, kv_len), ATTN_TOL)

    m = get_config(SSD_ARCH).ssm
    d_model = get_config(SSD_ARCH).d_model
    Hs, P, G, N = m.n_heads(d_model), m.head_dim, m.n_groups, m.d_state
    x = normal((1, S, Hs, P))
    dt = jax.nn.softplus(normal((1, S, Hs), jnp.float32))
    A = -jnp.exp(normal((Hs,), jnp.float32, 0.3))
    Bm, Cm = normal((1, S, G, N), scale=N ** -0.5), \
        normal((1, S, G, N), scale=N ** -0.5)
    _check_kernel("ssd_scan", functools.partial(
                      ssd_scan, chunk=m.chunk, return_final_state=True),
                  functools.partial(ref.ssd_chunked_ref, chunk=m.chunk,
                                    return_final_state=True),
                  (x, dt, A, Bm, Cm), SSD_TOL)


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------
def serve_phase() -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import lm
    from repro.models.params import init_params
    from repro.serve.engine import DecodeEngine, Request
    from repro.tune import cache as tune_cache

    cfg = get_config(ARCH)
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(SEED))
    for layout in ("dense", "paged"):
        tc = tune_cache.get_cache()
        hits0, misses0 = tc.hits, tc.misses
        eng = DecodeEngine(cfg, params, batch_slots=SLOTS, max_seq=MAX_SEQ,
                           rng_seed=SEED, mode="fused",
                           prefill_chunk=PREFILL_CHUNK, kv_layout=layout)
        rng = np.random.default_rng(SEED)
        reqs = []
        for _ in range(N_REQUESTS):
            plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
            prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            reqs.append(Request(prompt=prompt, max_new_tokens=NEW_TOKENS))
            eng.submit(reqs[-1])
        t0 = time.perf_counter()
        steps = eng.run_until_drained()
        wall = time.perf_counter() - t0
        tokens = sum(len(r.output) for r in reqs)
        complete = sum(r.done and not r.failed
                       and len(r.output) == NEW_TOKENS for r in reqs)
        log(f"serve {layout}: {complete}/{N_REQUESTS} requests complete, "
            f"{tokens} tokens, {steps} decode steps, wall {wall:.3f}s "
            f"(compilation included)")
        log(f"serve {layout} kv_stats: {eng.kv_stats()}")
        log(f"serve {layout} tune cache: hits {tc.hits - hits0} "
            f"misses {tc.misses - misses0}")
        if complete != N_REQUESTS:
            raise RuntimeError(f"serve {layout}: only {complete} of "
                               f"{N_REQUESTS} requests completed")
        del eng


# ---------------------------------------------------------------------------
# train phases
# ---------------------------------------------------------------------------
def _train(batch: int, steps: int, rules=None):
    """Losses and final parameters of ``steps`` AdamW steps."""
    import math

    from repro.configs import get_config
    from repro.data.synthetic import data_config_for
    from repro.train.loop import TrainJob, run_training

    cfg = get_config(ARCH)
    dc = data_config_for(cfg, seq_len=TRAIN_SEQ, batch_size=batch, seed=SEED)
    job = TrainJob(total_steps=steps, log_every=1, seed=SEED,
                   warmup=1)
    hist, _, params = run_training(cfg, dc, job, rules=rules, log=log)
    losses = [h["loss"] for h in hist]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train losses not finite: {losses}")
    return losses, params


def train_phase() -> None:
    losses, _ = _train(TRAIN_BATCH, TRAIN_STEPS)
    log(f"train {ARCH} seq {TRAIN_SEQ} batch {TRAIN_BATCH}: losses "
        f"{losses}")


def four_chip_phase() -> None:
    """Sharded training on a (data=2, model=2) mesh with ZeRO-1, against
    the same steps on one chip of the four."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.models.params import init_params
    from repro.sharding.rules import make_rules

    rules = make_rules(make_mesh((2, 2), ("data", "model")))
    sharded, p_sharded = _train(SHARDED_BATCH, SHARDED_STEPS, rules=rules)
    single, p_single = _train(SHARDED_BATCH, SHARDED_STEPS)
    diff = max(abs(a - b) for a, b in zip(sharded, single, strict=True))
    log(f"four-chip train: sharded {sharded} one-chip {single} "
        f"max |diff| {diff:.3e} (atol {SHARDED_LOSS_ATOL})")

    # the losses are read before each update; compare what the updates did
    chip = jax.devices()[0]
    p_sharded = jax.device_put(p_sharded, chip)
    p_init = init_params(lm.make_lm(get_config(ARCH)),
                         jax.random.PRNGKey(SEED))

    @jax.jit
    def dist(a, b):
        return jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b), strict=True)))

    step = float(dist(p_single, p_init))
    step_sharded = float(dist(p_sharded, p_init))
    apart = float(dist(p_sharded, p_single)) / step
    log(f"four-chip update norm: sharded {step_sharded:.6e} one-chip "
        f"{step:.6e}; |p_sharded - p_one_chip| / |update| {apart:.3e} "
        f"(rtol {SHARDED_UPDATE_RTOL})")
    if diff > SHARDED_LOSS_ATOL:
        raise RuntimeError("sharded loss does not match the one-chip loss")
    if not apart <= SHARDED_UPDATE_RTOL:
        raise RuntimeError("sharded update does not match the one-chip one")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase on a 2x2 "
                         "host and its one-chip comparison")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    device = require_chip(4 if args.four_chips else 1)
    phases = ([four_chip_phase] if args.four_chips
              else [kernel_phase, serve_phase, train_phase])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"{phase.__name__} done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
