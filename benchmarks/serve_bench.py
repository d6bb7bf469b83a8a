"""Serving fast-path benchmark: fused on-device decode loop vs per-step
host sync, sequential-force vs chunked prefill, and the decode-attention
kernel, under a seeded Poisson many-user request trace.

Sections (all on a CPU-sized 2-layer config so dispatch/host-sync overhead
— the thing the fused loop removes — dominates over model compute):

* ``throughput``: same greedy workload through ``mode="host"`` (the seed
  engine's per-step-host-sync cost profile: one decode dispatch, a full
  logits device->host transfer and per-slot python sampling per token)
  and ``mode="fused"`` (sampling + slot bookkeeping inside one jitted
  ``lax.scan``, one host sync per ``steps_per_sync`` steps).  Batched
  greedy outputs are asserted byte-identical to each request decoded
  alone, sequentially (continuous-batching invariance — slot contents
  never leak across slots); ``--smoke`` asserts the >= 5x tokens/sec
  floor through ``retry_measurement``.  Host-vs-fused outputs are *not*
  byte-compared: they are different XLA programs, and XLA does not
  guarantee bitwise-identical bf16 logits across program boundaries, so
  near-tie argmax rows may legitimately flip.
* ``prefill``: long prompts via sequential one-token-per-step forcing vs
  ``prefill_chunk`` batched admission (identical outputs asserted),
  recording decode steps, wall time and time-to-first-token.
* ``poisson_trace``: wall-clock replay of a seeded Poisson arrival trace
  with mixed prompt/output lengths; tokens/sec and p50/p99 inter-token
  gaps.  The fused engine observes tokens in ``steps_per_sync`` bursts,
  so its p99 gap reflects sync quantisation — the artifact records it
  rather than hiding it.
* ``decode_kernel``: the Sq=1 Pallas decode kernel (interpret mode)
  against the pure-jnp reference on a ragged GQA batch with non-dividing
  Sk, plus XLA-path timing.
* ``memory``: paged vs dense KV under the *same* HBM budget.  The dense
  engine's ``slots x max_seq`` KV bytes buy exactly ``slots x
  ceil(max_seq/page_size)`` pool pages; with a short-request mix the
  paged engine runs >= 4x the concurrent slots in that budget
  (``peak_occupied`` asserted), at >= 0.9x the fused-dense tokens/sec on
  the equal-slots workload (floor via ``retry_measurement`` under
  ``--smoke``).  An ``admission_scaling`` subsection reruns a hybrid
  (attention+SSM) config at two ``max_seq`` values and asserts the
  ``admit_cache_elems`` counter scales with ``max_seq`` for dense but
  stays flat for paged — admission no longer round-trips KV stripes.

Results land in BENCH_serve.json at the repo root.

Usage:
    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax                                           # noqa: E402
import numpy as np                                   # noqa: E402

from sim_scale_bench import retry_measurement        # noqa: E402

from repro.configs import reduced_config             # noqa: E402
from repro.configs.registry import with_segment_counts  # noqa: E402
from repro.models import lm                          # noqa: E402
from repro.models.params import init_params, is_param  # noqa: E402
from repro.serve.engine import DecodeEngine, Request  # noqa: E402
from repro.serve.trace import poisson_trace          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "smollm-360m"
MAX_SEQ = 128
SLOTS = 4


def _cfg_params():
    cfg = with_segment_counts(reduced_config(ARCH), [2])
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _workload(cfg, n, *, seed=7, plen=(5, 12), max_new=16):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        L = int(rng.integers(plen[0], plen[1] + 1))
        out.append((rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new))
    return out


def _run(cfg, params, work, **engine_kw):
    eng = DecodeEngine(cfg, params, max_seq=MAX_SEQ, **engine_kw)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    steps = eng.run_until_drained()
    wall = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    outputs = [[int(np.asarray(t)) for t in r.output] for r in reqs]
    return {"tokens": toks, "steps": steps, "wall_s": round(wall, 4),
            "tok_s": round(toks / wall, 2)}, outputs


def _warmup(cfg, params, *, plen=(5, 12), **engine_kw):
    # prompts must be long enough to exercise every program the timed run
    # will hit (e.g. a full prefill chunk), or compilation lands in-region
    _run(cfg, params, _workload(cfg, 2, seed=1, plen=plen, max_new=3),
         **engine_kw)


# ---------------------------------------------------------------------------
# throughput: host-sync-per-step vs fused loop
# ---------------------------------------------------------------------------
def bench_throughput(out, cfg, params, *, smoke: bool):
    n = 12 if smoke else 24
    work = _workload(cfg, n, plen=(4, 8), max_new=24)
    _warmup(cfg, params, mode="host", batch_slots=SLOTS)
    _warmup(cfg, params, mode="fused", batch_slots=SLOTS, steps_per_sync=16)

    def measure():
        host, _ = _run(cfg, params, work, mode="host", batch_slots=SLOTS)
        fused, out_f = _run(cfg, params, work, mode="fused",
                            batch_slots=SLOTS, steps_per_sync=16)
        return {"host": host, "fused": fused,
                "speedup": round(fused["tok_s"] / host["tok_s"], 2),
                "outputs": out_f}

    rec = measure()
    if smoke:
        rec = retry_measurement(
            out, "fused_speedup", rec, measure,
            accept=lambda r: r["speedup"] >= 5.0,
            best=lambda a, b: a if a["speedup"] >= b["speedup"] else b,
            retries=2)
        assert rec["speedup"] >= 5.0, \
            f"fused loop speedup {rec['speedup']}x < 5x floor"

    # continuous-batching invariance: batched greedy == each request decoded
    # alone, one after another, through the same fused program (same engine
    # geometry, so slot isolation is the only thing under test — not
    # cross-program fp reproducibility, which XLA does not promise)
    solo = []
    for p, m in work:
        _, o = _run(cfg, params, [(p, m)], mode="fused",
                    batch_slots=SLOTS, steps_per_sync=16)
        solo.append(o[0])
    assert rec.pop("outputs") == solo, \
        "batched greedy outputs != single-request sequential decode"
    rec["solo_identity"] = True
    out["throughput"] = rec
    print(f"[throughput] host {rec['host']['tok_s']} tok/s, "
          f"fused {rec['fused']['tok_s']} tok/s "
          f"({rec['speedup']}x, identity ok)")


# ---------------------------------------------------------------------------
# prefill: sequential forcing vs chunked admission
# ---------------------------------------------------------------------------
def _run_ttft(cfg, params, work, **engine_kw):
    """Like _run but records time-to-first-token per request."""
    eng = DecodeEngine(cfg, params, max_seq=MAX_SEQ, **engine_kw)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
    for r in reqs:
        eng.submit(r)
    ttft = [None] * len(reqs)
    t0 = time.perf_counter()
    while (eng.queue or any(s is not None for s in eng.slot_req)) \
            and eng.steps < 100_000:
        eng.step()
        now = time.perf_counter() - t0
        for i, r in enumerate(reqs):
            if ttft[i] is None and r.output:
                ttft[i] = now
    wall = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    outputs = [[int(np.asarray(t)) for t in r.output] for r in reqs]
    return {"tokens": toks, "steps": eng.steps, "wall_s": round(wall, 4),
            "tok_s": round(toks / wall, 2),
            "ttft_mean_s": round(float(np.mean(ttft)), 4)}, outputs


def bench_prefill(out, cfg, params, *, smoke: bool):
    n = 6 if smoke else 12
    work = _workload(cfg, n, seed=11, plen=(36, 56), max_new=4)
    chunk_kw = dict(prefill_chunk=16, max_prefill_tokens_per_sync=32)
    _warmup(cfg, params, plen=(36, 56), mode="fused", batch_slots=SLOTS)
    _warmup(cfg, params, plen=(36, 56), mode="fused", batch_slots=SLOTS,
            **chunk_kw)
    seq, out_s = _run_ttft(cfg, params, work, mode="fused",
                           batch_slots=SLOTS)
    chunked, out_c = _run_ttft(cfg, params, work, mode="fused",
                               batch_slots=SLOTS, **chunk_kw)
    assert out_s == out_c, "chunked prefill changed greedy outputs"
    assert chunked["steps"] < seq["steps"], \
        "chunked prefill should need fewer decode steps"
    out["prefill"] = {"sequential_force": seq, "chunked": chunked,
                      "chunk": 16, "identity": True}
    print(f"[prefill] sequential {seq['steps']} steps / {seq['wall_s']}s, "
          f"chunked {chunked['steps']} steps / {chunked['wall_s']}s")


# ---------------------------------------------------------------------------
# poisson trace replay
# ---------------------------------------------------------------------------
def _replay(cfg, params, trace, **engine_kw):
    eng = DecodeEngine(cfg, params, max_seq=MAX_SEQ, **engine_kw)
    reqs = [Request(prompt=t.prompt, max_new_tokens=t.max_new_tokens,
                    temperature=t.temperature) for t in trace]
    stamps: list[list[float]] = [[] for _ in reqs]   # arrival + per-token
    nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while nxt < len(reqs) and trace[nxt].arrival_s <= now:
            eng.submit(reqs[nxt])
            stamps[nxt].append(now)
            nxt += 1
        busy = eng.queue or any(s is not None for s in eng.slot_req)
        if not busy:
            if nxt >= len(reqs):
                break
            time.sleep(min(trace[nxt].arrival_s - now, 0.005))
            continue
        eng.step()
        now = time.perf_counter() - t0
        for i, r in enumerate(reqs):
            while len(stamps[i]) - 1 < len(r.output):
                stamps[i].append(now)
    wall = time.perf_counter() - t0
    gaps = np.concatenate([np.diff(s) for s in stamps if len(s) > 1])
    toks = sum(len(r.output) for r in reqs)
    return {"tokens": toks, "wall_s": round(wall, 3),
            "tok_s": round(toks / wall, 2),
            "gap_p50_ms": round(float(np.percentile(gaps, 50)) * 1e3, 3),
            "gap_p99_ms": round(float(np.percentile(gaps, 99)) * 1e3, 3)}


def bench_poisson(out, cfg, params, *, smoke: bool):
    n = 16 if smoke else 48
    trace = poisson_trace(n_requests=n, rate_per_s=40.0,
                          vocab_size=cfg.vocab_size, seed=3,
                          prompt_lens=(4, 16), output_lens=(4, 12))
    _warmup(cfg, params, mode="host", batch_slots=SLOTS)
    _warmup(cfg, params, mode="fused", batch_slots=SLOTS, steps_per_sync=8)
    out["poisson_trace"] = {
        "requests": n, "rate_per_s": 40.0,
        "host": _replay(cfg, params, trace, mode="host", batch_slots=SLOTS),
        "fused": _replay(cfg, params, trace, mode="fused",
                         batch_slots=SLOTS, steps_per_sync=8),
        "note": "fused p99 gap includes steps_per_sync burst quantisation",
    }
    h, f = out["poisson_trace"]["host"], out["poisson_trace"]["fused"]
    print(f"[poisson] host {h['tok_s']} tok/s p99 {h['gap_p99_ms']}ms; "
          f"fused {f['tok_s']} tok/s p99 {f['gap_p99_ms']}ms")


# ---------------------------------------------------------------------------
# decode-attention kernel
# ---------------------------------------------------------------------------
def bench_decode_kernel(out, *, smoke: bool):
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.ref import decode_attention_ref

    B, S, H, K, D = 4, 100, 8, 2, 32          # non-dividing Sk, GQA 4:1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, D), jax.numpy.float32)
    k = jax.random.normal(ks[1], (K, B, S, D), jax.numpy.float32)
    v = jax.random.normal(ks[2], (K, B, S, D), jax.numpy.float32)
    kv_len = jax.numpy.asarray([7, 31, 64, 100], jax.numpy.int32)
    got = decode_attention(q, k, v, kv_len, block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, kv_len)
    diff = float(jax.numpy.max(jax.numpy.abs(got - ref)))
    assert diff < 2e-5, f"decode kernel vs ref diff {diff}"

    ref_jit = jax.jit(decode_attention_ref)
    ref_jit(q, k, v, kv_len).block_until_ready()
    reps = 20 if smoke else 100
    t0 = time.perf_counter()
    for _ in range(reps):
        ref_jit(q, k, v, kv_len).block_until_ready()
    ref_ms = (time.perf_counter() - t0) / reps * 1e3
    out["decode_kernel"] = {
        "shape": {"B": B, "Sk": S, "H": H, "kv_heads": K, "head_dim": D},
        "kv_len": [int(x) for x in kv_len],
        "max_abs_diff_vs_ref": diff,
        "xla_ref_ms": round(ref_ms, 3),
        "note": "Pallas kernel validated in interpret mode on the CPU; "
                "chip_smoke.py runs the compiled kernel on a TPU",
    }
    print(f"[decode_kernel] interpret vs ref diff {diff:.2e}, "
          f"xla ref {ref_ms:.2f}ms")


# ---------------------------------------------------------------------------
# memory: paged vs dense KV in the same HBM budget
# ---------------------------------------------------------------------------
def _kv_bytes(cfg, *, slots, max_seq, paged=None):
    """KV bytes from the cache descriptor tree (leaves with a seq_kv axis)."""
    descr = jax.tree_util.tree_leaves(
        lm.make_cache(cfg, slots, max_seq, paged=paged), is_leaf=is_param)
    return sum(int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
               for p in descr if "seq_kv" in p.logical)


def _run_eng(cfg, params, work, **engine_kw):
    eng = DecodeEngine(cfg, params, max_seq=MAX_SEQ, **engine_kw)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_drained()
    wall = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    assert all(r.done and not r.failed for r in reqs)
    return {"tokens": toks, "wall_s": round(wall, 4),
            "tok_s": round(toks / wall, 2)}, eng


def bench_memory(out, cfg, params, *, smoke: bool):
    ps = 16
    width = -(-MAX_SEQ // ps)
    budget_pages = SLOTS * width      # pool bytes == dense slots x max_seq
    paged_slots = SLOTS * 4
    dense_bytes = _kv_bytes(cfg, slots=SLOTS, max_seq=MAX_SEQ)
    paged_bytes = _kv_bytes(cfg, slots=paged_slots, max_seq=MAX_SEQ,
                            paged=(budget_pages, ps))
    assert paged_bytes <= dense_bytes, (paged_bytes, dense_bytes)

    # short-request mix: prompt+output fit in one page, so the pool admits
    # 4x the dense slot count concurrently inside the same byte budget
    paged_kw = dict(mode="fused", batch_slots=paged_slots, steps_per_sync=4,
                    kv_layout="paged", page_size=ps, num_pages=budget_pages)
    n = paged_slots + 8
    work = _workload(cfg, n, seed=13, plen=(4, 6), max_new=8)
    _warmup(cfg, params, plen=(4, 6), **paged_kw)
    conc, eng = _run_eng(cfg, params, work, **paged_kw)
    ks = eng.kv_stats()
    assert ks["peak_occupied"] >= 4 * SLOTS, \
        f"paged held {ks['peak_occupied']} concurrent slots in the dense " \
        f"budget, expected >= {4 * SLOTS}"

    # throughput parity at equal slot count: paging indirection must not
    # tax the fused decode loop by more than 10% on the CPU smoke config
    par_work = _workload(cfg, 12 if smoke else 24, plen=(4, 8), max_new=24)
    par_dense = dict(mode="fused", batch_slots=SLOTS, steps_per_sync=16)
    par_paged = dict(par_dense, kv_layout="paged", page_size=ps,
                     num_pages=budget_pages)
    _warmup(cfg, params, **par_dense)
    _warmup(cfg, params, **par_paged)

    def measure():
        # best-of-3 per side: single CPU runs jitter ~10%, which would
        # swamp the <10% tax the floor is meant to police
        d = max((_run_eng(cfg, params, par_work, **par_dense)[0]
                 for _ in range(3)), key=lambda r: r["tok_s"])
        p = max((_run_eng(cfg, params, par_work, **par_paged)[0]
                 for _ in range(3)), key=lambda r: r["tok_s"])
        return {"dense": d, "paged": p,
                "ratio": round(p["tok_s"] / d["tok_s"], 3)}

    parity = measure()
    if smoke:
        parity = retry_measurement(
            out, "paged_parity", parity, measure,
            accept=lambda r: r["ratio"] >= 0.9,
            best=lambda a, b: a if a["ratio"] >= b["ratio"] else b,
            retries=2)
        assert parity["ratio"] >= 0.9, \
            f"paged throughput {parity['ratio']}x dense < 0.9x floor"

    out["memory"] = {
        "page_size": ps, "num_pages": budget_pages,
        "dense_kv_bytes": dense_bytes, "paged_kv_bytes": paged_bytes,
        "dense_slots": SLOTS, "paged_slots": paged_slots,
        "peak_occupied": ks["peak_occupied"],
        "high_water_pages": ks["high_water"],
        "preemptions": ks["preemptions"],
        "concurrency": conc, "throughput_parity": parity,
        "admission_scaling": _admission_scaling(),
    }
    print(f"[memory] {ks['peak_occupied']} concurrent slots in the "
          f"{dense_bytes >> 10}KiB dense budget ({SLOTS} dense slots), "
          f"parity {parity['ratio']}x")


def _admission_scaling():
    """Hybrid (attention+SSM) admission cost: dense round-trips the whole
    cache per admission (scales with max_seq); paged touches O(1) state
    plus the pages actually allocated."""
    cfg = reduced_config("jamba-v0.1-52b")
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    work = [(np.arange(4, dtype=np.int32) + 1, 2) for _ in range(2)]

    def elems(max_seq, **kw):
        eng = DecodeEngine(cfg, params, batch_slots=2, max_seq=max_seq,
                           steps_per_sync=2, **kw)
        for p, m in work:
            eng.submit(Request(prompt=p, max_new_tokens=m))
        eng.run_until_drained()
        return eng.stats["admit_cache_elems"]

    rec = {"dense_64": elems(64), "dense_128": elems(128),
           "paged_64": elems(64, kv_layout="paged", page_size=8),
           "paged_128": elems(128, kv_layout="paged", page_size=8)}
    assert rec["dense_128"] > rec["dense_64"], \
        "dense admission cost should scale with max_seq"
    assert rec["paged_128"] == rec["paged_64"], \
        "paged admission cost must not scale with max_seq"
    print(f"[memory] admission elems: dense {rec['dense_64']}->"
          f"{rec['dense_128']} vs paged {rec['paged_64']}->"
          f"{rec['paged_128']} (64->128 max_seq)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + hard floors, for CI")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_serve.json"))
    args = ap.parse_args(argv)

    cfg, params = _cfg_params()
    out: dict = {"arch": ARCH, "layers": 2, "slots": SLOTS,
                 "max_seq": MAX_SEQ, "smoke": bool(args.smoke),
                 "backend": jax.default_backend()}
    bench_decode_kernel(out, smoke=args.smoke)
    bench_throughput(out, cfg, params, smoke=args.smoke)
    bench_prefill(out, cfg, params, smoke=args.smoke)
    bench_poisson(out, cfg, params, smoke=args.smoke)
    bench_memory(out, cfg, params, smoke=args.smoke)

    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[serve_bench] wrote {args.out}")


if __name__ == "__main__":
    main()
