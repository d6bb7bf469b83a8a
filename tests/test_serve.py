"""Serving engine: continuous batching isolation, sampling, drain."""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import lm
from repro.models.params import init_params
from repro.serve.engine import DecodeEngine, Request
from repro.serve.sampler import sample, sample_batch
import jax.numpy as jnp


def _engine(arch, slots=2, max_seq=64, **kw):
    cfg = reduced_config(arch)
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    return cfg, DecodeEngine(cfg, params, batch_slots=slots,
                             max_seq=max_seq, **kw)


def _params(cfg):
    return init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))


def test_sampler_greedy_and_topk():
    logits = jnp.array([0.1, 5.0, -1.0, 2.0])
    assert int(sample(logits, jax.random.PRNGKey(0))) == 1
    t = sample(logits, jax.random.PRNGKey(0), temperature=1.0, top_k=2)
    assert int(t) in (1, 3)


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m"])
def test_continuous_batching_isolation(arch):
    """A request's greedy output must be identical whether it runs alone or
    alongside other requests in different slots (SSM state gating)."""
    cfg, eng1 = _engine(arch, slots=1)
    r_alone = Request(prompt=np.arange(5, dtype=np.int32) + 1,
                      max_new_tokens=6)
    eng1.submit(r_alone)
    eng1.run_until_drained()

    cfg, eng2 = _engine(arch, slots=3)
    r_same = Request(prompt=np.arange(5, dtype=np.int32) + 1,
                     max_new_tokens=6)
    other1 = Request(prompt=np.arange(9, dtype=np.int32) + 7,
                     max_new_tokens=9)
    other2 = Request(prompt=np.arange(3, dtype=np.int32) + 40,
                     max_new_tokens=4)
    eng2.submit(other1)
    eng2.submit(r_same)
    eng2.submit(other2)
    eng2.run_until_drained()
    assert [int(t) for t in r_alone.output] == \
        [int(t) for t in r_same.output]


def test_more_requests_than_slots_all_complete():
    cfg, eng = _engine("smollm-360m", slots=2)
    reqs = [Request(prompt=np.array([i + 1, i + 2], np.int32),
                    max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done and len(r.output) == 3 for r in reqs)


def test_sample_batch_greedy_and_tiebreak():
    logits = jnp.array([[0.1, 5.0, -1.0, 2.0],
                        [1.0, 5.0, 5.0, 0.0]])     # row 1: exact tie
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    toks = sample_batch(logits, keys, jnp.zeros(2), jnp.zeros(2, jnp.int32))
    assert toks.tolist() == [1, 1], \
        "greedy must pick argmax, ties broken by lowest index"


def test_sample_batch_per_slot_topk():
    logits = jnp.tile(jnp.array([0.0, 4.0, 3.0, 2.0]), (2, 1))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2) + 9)
    toks = sample_batch(logits, keys, jnp.full(2, 5.0),
                        jnp.array([1, 2], jnp.int32))
    assert int(toks[0]) == 1                       # top-1 == forced argmax
    assert int(toks[1]) in (1, 2)                  # top-2 restricted support


def test_sample_batch_independent_streams():
    """Two slots with *identical* logits and temperature > 0 must draw from
    independent per-slot RNG streams (regression: the seed engine shared one
    key across slots, so identical logits always produced identical draws)."""
    logits = jnp.zeros((2, 64))                    # flat: draw is pure noise
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    draws = np.stack([
        np.asarray(sample_batch(
            logits, jax.vmap(jax.random.fold_in, (0, None))(keys, i),
            jnp.ones(2), jnp.zeros(2, jnp.int32)))
        for i in range(8)])
    assert not np.array_equal(draws[:, 0], draws[:, 1]), \
        "slots sharing RNG: identical logits produced identical draws"


def test_engine_rng_independent_across_slots():
    """Two temperature>0 requests with the same prompt running concurrently
    must not emit identical token streams."""
    cfg, eng = _engine("smollm-360m", slots=2)
    prompt = np.arange(4, dtype=np.int32) + 1
    a = Request(prompt=prompt, max_new_tokens=12, temperature=1.0)
    b = Request(prompt=prompt.copy(), max_new_tokens=12, temperature=1.0)
    eng.submit(a)
    eng.submit(b)
    eng.run_until_drained()
    assert [int(t) for t in a.output] != [int(t) for t in b.output]


@pytest.mark.parametrize("mode", ["fused", "host"])
def test_staggered_interleave_matches_solo(mode):
    """K requests with staggered admissions/retirements (more requests than
    slots, mixed lengths) decode greedily to exactly what each produces run
    alone, sequentially, through the same engine geometry."""
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    rng = np.random.default_rng(5)
    work = [(rng.integers(0, cfg.vocab_size,
                          int(rng.integers(2, 7))).astype(np.int32),
             int(rng.integers(2, 9))) for _ in range(5)]
    kw = dict(batch_slots=2, max_seq=64, mode=mode, steps_per_sync=4)

    eng = DecodeEngine(cfg, params, **kw)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    batched = [[int(t) for t in r.output] for r in reqs]

    for (p, m), got in zip(work, batched):
        solo_eng = DecodeEngine(cfg, params, **kw)
        solo = Request(prompt=p, max_new_tokens=m)
        solo_eng.submit(solo)
        solo_eng.run_until_drained()
        assert got == [int(t) for t in solo.output]


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-4b"])
def test_chunked_prefill_identity(arch):
    """Chunked prefill admission must reproduce sequential one-token-per-step
    prompt forcing byte-for-byte (attention archs: cache scatter is exact;
    SSD-scan archs recombine chunks in fp and are covered by tolerance tests
    in test_models)."""
    cfg = reduced_config(arch)
    params = _params(cfg)
    rng = np.random.default_rng(2)
    work = [(rng.integers(0, cfg.vocab_size,
                          int(rng.integers(9, 20))).astype(np.int32), 4)
            for _ in range(3)]

    def run(**extra):
        eng = DecodeEngine(cfg, params, batch_slots=2, max_seq=64,
                           steps_per_sync=4, **extra)
        reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
        for r in reqs:
            eng.submit(r)
        steps = eng.run_until_drained()
        return [[int(t) for t in r.output] for r in reqs], steps

    seq, seq_steps = run()
    chunked, chunked_steps = run(prefill_chunk=4,
                                 max_prefill_tokens_per_sync=8)
    assert seq == chunked
    assert chunked_steps < seq_steps


def test_host_mode_drains_and_matches_lengths():
    cfg, eng = _engine("smollm-360m", slots=2, mode="host")
    reqs = [Request(prompt=np.array([i + 1, i + 2], np.int32),
                    max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done and len(r.output) == 3 for r in reqs)


# ---------------------------------------------------------------------------
# paged KV layout
# ---------------------------------------------------------------------------
def _run_mix(cfg, params, work, **kw):
    eng = DecodeEngine(cfg, params, **kw)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in work]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return [[int(t) for t in r.output] for r in reqs], reqs, eng


@pytest.mark.parametrize("arch", ["smollm-360m", "deepseek-v3-671b",
                                  "jamba-v0.1-52b"])
def test_paged_matches_dense_greedy(arch):
    """Paged KV must reproduce dense greedy token-for-token across plain
    GQA, MLA, and hybrid attention/SSM stacks, with ragged lengths."""
    cfg = reduced_config(arch)
    params = _params(cfg)
    rng = np.random.default_rng(3)
    work = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(2, 14))).astype(np.int32),
             int(rng.integers(3, 8))) for _ in range(5)]
    kw = dict(batch_slots=3, max_seq=40, steps_per_sync=4)
    dense, _, _ = _run_mix(cfg, params, work, **kw)
    paged, reqs, eng = _run_mix(cfg, params, work, kv_layout="paged",
                                page_size=8, **kw)
    assert dense == paged
    assert all(r.done and not r.failed for r in reqs)
    assert eng.pool.used_pages == 0          # all pages returned on drain


def test_paged_non_dividing_page_size():
    """page_size that divides neither max_seq nor typical lengths: the
    partial last page must mask correctly end to end."""
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    rng = np.random.default_rng(4)
    work = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(2, 20))).astype(np.int32), 6)
            for _ in range(4)]
    kw = dict(batch_slots=2, max_seq=60, steps_per_sync=4)
    dense, _, _ = _run_mix(cfg, params, work, **kw)
    paged, _, _ = _run_mix(cfg, params, work, kv_layout="paged",
                           page_size=7, **kw)
    assert dense == paged


def test_paged_prefill_chunk_matches_dense_chunked():
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    rng = np.random.default_rng(6)
    work = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(9, 20))).astype(np.int32), 4)
            for _ in range(3)]
    kw = dict(batch_slots=2, max_seq=64, steps_per_sync=4, prefill_chunk=4)
    dense, _, _ = _run_mix(cfg, params, work, **kw)
    paged, _, _ = _run_mix(cfg, params, work, kv_layout="paged",
                           page_size=8, **kw)
    assert dense == paged


def test_paged_pool_exhaustion_preempts_and_completes():
    """A pool far too small for the offered load must preempt (youngest
    first) yet still complete every request exactly once, with outputs
    identical to dense — at-least-once requeue, no deadlock, no loss."""
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    rng = np.random.default_rng(7)
    work = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(6, 14))).astype(np.int32), 12)
            for _ in range(8)]
    kw = dict(batch_slots=4, max_seq=40, steps_per_sync=4)
    dense, _, _ = _run_mix(cfg, params, work, **kw)
    # width = ceil(40/8) = 5; 6 pages can't back two long slots at once
    paged, reqs, eng = _run_mix(cfg, params, work, kv_layout="paged",
                                page_size=8, num_pages=6, **kw)
    assert eng.stats["preemptions"] >= 1
    assert all(r.done and not r.failed for r in reqs)
    assert [len(o) for o in paged] == [m for _, m in work]  # exactly once
    assert dense == paged


def test_paged_rejects_bad_prompts_and_keeps_serving():
    """Regression: malformed prompts used to assert-crash the engine.  Now
    they fail typed and everyone else is served."""
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    for layout in ({"kv_layout": "dense"},
                   {"kv_layout": "paged", "page_size": 8}):
        eng = DecodeEngine(cfg, params, batch_slots=2, max_seq=16, **layout)
        empty = Request(prompt=np.zeros((0,), np.int32))
        good = Request(prompt=np.array([3, 4, 5], np.int32),
                       max_new_tokens=4)
        long = Request(prompt=np.ones((16,), np.int32))
        for r in (empty, good, long):
            eng.submit(r)
        eng.run_until_drained()
        assert empty.failed and "length 0" in empty.fail_reason
        assert long.failed and "length 16" in long.fail_reason
        assert good.done and not good.failed and len(good.output) == 4
        assert eng.stats["rejected"] == 2


def test_paged_admission_cost_independent_of_max_seq():
    """Satellite: dense admission round-trips the whole cache (scales with
    max_seq on stateful archs); paged touches only O(1) state + the pages
    actually allocated."""
    cfg = reduced_config("jamba-v0.1-52b")
    params = _params(cfg)
    work = [(np.arange(4, dtype=np.int32) + 1, 2) for _ in range(2)]

    def elems(max_seq, **kw):
        _, _, eng = _run_mix(cfg, params, work, batch_slots=2,
                             max_seq=max_seq, steps_per_sync=2, **kw)
        return eng.stats["admit_cache_elems"]

    d64, d128 = elems(64), elems(128)
    p64 = elems(64, kv_layout="paged", page_size=8)
    p128 = elems(128, kv_layout="paged", page_size=8)
    assert d128 > d64          # dense admission scales with max_seq
    assert p128 == p64         # paged admission does not
    assert p64 < d64


def test_paged_host_mode_matches_host_dense():
    cfg = reduced_config("smollm-360m")
    params = _params(cfg)
    rng = np.random.default_rng(8)
    work = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(2, 10))).astype(np.int32), 5)
            for _ in range(4)]
    kw = dict(batch_slots=2, max_seq=40, mode="host")
    dense, _, _ = _run_mix(cfg, params, work, **kw)
    paged, _, _ = _run_mix(cfg, params, work, kv_layout="paged",
                           page_size=8, **kw)
    assert dense == paged


def test_musicgen_codebook_outputs():
    cfg, eng = _engine("musicgen-medium", slots=1)
    prompt = np.ones((3, cfg.num_codebooks), np.int32)
    r = Request(prompt=prompt, max_new_tokens=2)
    eng.submit(r)
    eng.run_until_drained()
    assert len(r.output) == 2
    assert r.output[0].shape == (cfg.num_codebooks,)


ROW_KEYS = ("prefill_rows", "prefill_rows_active", "decode_rows",
            "decode_rows_live", "decode_rows_forced", "decode_rows_emitted")


def _rows(eng) -> dict:
    return {k: eng.stats[k] for k in ROW_KEYS}


def test_row_counters_one_request():
    """A 10-token prompt with 4-token chunks: two chunk calls compute 4 slots
    x 4 rows each, one slot's rows active; position 8 is forced decode
    (prompt token 9 replaces the sample), then 5 tokens are emitted, over
    two syncs of 4 slots x 4 steps."""
    cfg, eng = _engine("smollm-360m", slots=4, max_seq=64, prefill_chunk=4,
                       steps_per_sync=4)
    eng.submit(Request(prompt=np.arange(10, dtype=np.int32) + 1,
                       max_new_tokens=5))
    eng.run_until_drained()
    assert _rows(eng) == {"prefill_rows": 32, "prefill_rows_active": 8,
                          "decode_rows": 32, "decode_rows_live": 6,
                          "decode_rows_forced": 1, "decode_rows_emitted": 5}


@pytest.mark.parametrize("mode", ["fused", "host"])
@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_row_counters_known_counts(mode, kv_layout):
    """Prompts of 3, 7, 10 and 13 tokens prefill 0, 4, 8 and 12 of them in
    4-token chunks: three chunk calls of 16 rows (12, 8 and 4 active), and
    2, 2, 1 and 0 prompt tokens go through forced decode."""
    kw = {"page_size": 8} if kv_layout == "paged" else {}
    cfg, eng = _engine("smollm-360m", slots=4, max_seq=64, prefill_chunk=4,
                       steps_per_sync=4, mode=mode, kv_layout=kv_layout, **kw)
    reqs = [Request(prompt=np.arange(L, dtype=np.int32) + 1, max_new_tokens=n)
            for L, n in ((3, 2), (7, 3), (10, 5), (13, 4))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    rows = _rows(eng)
    assert rows == {"prefill_rows": 48, "prefill_rows_active": 24,
                    "decode_rows": 4 * eng.steps, "decode_rows_live": 19,
                    "decode_rows_forced": 5, "decode_rows_emitted": 14}
    assert rows["decode_rows_forced"] + rows["decode_rows_emitted"] \
        == rows["decode_rows_live"]
    assert sum(len(r.output) for r in reqs) == rows["decode_rows_emitted"]
