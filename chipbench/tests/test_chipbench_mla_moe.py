"""The MLA + MoE reference (``reference/mla_moe.py``) against the program,
at a small size on the CPU with seeded random weights: a held share of the
experts, dropless routing, prefill then decode through the latent cache,
a whole serving run, and the three readers of the new scopes."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

import run
from harness import spec, weights
from harness import trace as T
from harness import program_trace as P
from harness.spec import BENCH, Cell, metric_reader
from reference import mla_moe
from reference.common import HIGHEST

SEED = 2 ** 34 + 77
FULL = os.path.join(BENCH, "configs", "deepseek-v2-lite-ep8.json")


def config(dtype="float32", first=2, count=4, experts=8, **kw) -> dict:
    """A DeepSeek-V2 configuration file at a small size: published keys,
    the held experts, and the registry's fields for them."""
    with open(FULL) as fh:
        full = json.load(fh)
    c = {"name": "tiny-mla-moe", "hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": count, "n_shared_experts": 2,
         "num_experts_per_tok": 3, "vocab_size": 256,
         "rope_theta": 10000, "rope_scaling": full["rope_scaling"],
         "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
         "scoring_func": "softmax", "norm_topk_prob": False,
         "routed_scaling_factor": 1, "dtype": dtype, "family": "moe",
         "reference": "mla_moe", "limits": full["limits"],
         "held_experts": {"first": first, "count": count, "of": experts}}
    c.update(kw)
    rs = c["rope_scaling"]
    c["model_config"] = {
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "head_dim": c["qk_nope_head_dim"],
        "d_ff": c["intermediate_size"], "d_ff_dense": c["intermediate_size"],
        "vocab_size": c["vocab_size"], "attention_kind": "mla",
        "mla": {"q_lora_rank": 0, "kv_lora_rank": c["kv_lora_rank"],
                "qk_nope_head_dim": c["qk_nope_head_dim"],
                "qk_rope_head_dim": c["qk_rope_head_dim"],
                "v_head_dim": c["v_head_dim"]},
        "moe": {"num_experts": experts,
                "num_shared_experts": c["n_shared_experts"],
                "top_k": c["num_experts_per_tok"],
                "d_ff_expert": c["moe_intermediate_size"],
                "first_k_dense": c["first_k_dense_replace"],
                "held_first": first, "held_count": count},
        "rope_theta": float(c["rope_theta"]), "rope_interleaved": True,
        "yarn_factor": float(rs["factor"]),
        "yarn_original_max_positions": rs["original_max_position_embeddings"],
        "yarn_beta_fast": float(rs["beta_fast"]),
        "yarn_beta_slow": float(rs["beta_slow"]),
        "yarn_mscale": rs["mscale"], "yarn_mscale_all_dim": rs["mscale_all_dim"],
        "act": "silu", "norm_eps": c["rms_norm_eps"], "tie_embeddings": False,
        "dtype": dtype}
    return c


def _setup(**kw):
    c = config(**kw)
    model, gen = tiny.model(c)
    return c, spec.program_config(c), model, gen(weights.seed_words(SEED))


def _logits(model, w, tokens):
    h = model.final_hidden(w, tokens)
    return jnp.matmul(h, model.head_matrix(w), precision=HIGHEST)


# -- the configuration and its weights ----------------------------------------
def test_full_size_weights_have_the_programs_tree():
    """The cell's file: the registry's model at one chip's share, weights
    laid out as the program's tree (3.11B parameters)."""
    from repro.configs import get_config
    from repro.models import lm

    with open(FULL) as fh:
        c = json.load(fh)
    pcfg = spec.program_config(c)
    reg = get_config("deepseek-v2-lite")
    assert pcfg == reg.replace(name=c["name"], moe=dataclasses.replace(
        reg.moe, held_count=8))
    model = spec.reference_model(c)
    gen = weights.make_generator(model.layout(), model.layers, jnp.bfloat16)
    shapes = model.to_program(jax.eval_shape(gen, weights.seed_words(7)))
    weights.check_matches(shapes, jax.eval_shape(
        lambda: lm.init_lm(pcfg, jax.random.PRNGKey(0))))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 3_110_989_312
    assert (model.layers, model.all_layers) == (26, 27)


def test_arithmetic_by_hand():
    with open(FULL) as fh:
        m = spec.reference_model(json.load(fh))
    # scores over 512 + 64 and values over 512, 16 heads, 2 per multiply-add
    assert m.mla_decode_work(1000) == (2 * 16 * (2 * 512 + 64) * 1000,
                                       1000 * 576 * 2)
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 * 2
            + 16 * 128 * 2048)
    dense = attn + 3 * 2048 * 10944
    expert = attn + 2048 * 64 + 3 * 2048 * 1408 * (2 + 6 * 8 / 64)
    want = 2 * (dense + 26 * expert + 2048 * 102400) \
        + 27 * 2 * 16 * 1088 * 1000
    assert m.decode_token_flops(1000) == pytest.approx(want, rel=1e-12)


# -- the reference against the program ----------------------------------------
def _layer_input(c, w, T=40):
    x = jax.random.normal(jax.random.PRNGKey(3), (T, c["hidden_size"]))
    return {k: w[k][0] for k in mla_moe.ATTN + mla_moe.MOE_FFN}, x


def test_shares_add_up_against_the_reference():
    """The uncut layer's experts split into two shares (0-3 and 4-7): each
    share's program layer equals the reference's share, and the routed
    parts of both, with the shared experts once, equal the reference's
    uncut layer."""
    from repro.models import moe

    c, pcfg, whole, w = _setup(first=0, count=8)
    lw, x = _layer_input(c, w)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               whole.to_program(w)["segments"][1]["ffn"])
    shared = mla_moe.swiglu(x, lw["s_up"], lw["s_gate"], lw["s_down"])
    summed = shared
    for first in (0, 4):
        part = slice(first, first + 4)
        spec4 = dataclasses.replace(whole.spec, held=4, held_first=first)
        ref = mla_moe.moe(spec4, {**lw, **{k: lw[k][part] for k in
                                           ("wi", "wg", "wo_e")}}, x)
        cfg4 = pcfg.replace(moe=dataclasses.replace(
            pcfg.moe, held_first=first, held_count=4))
        y, _ = moe.apply_moe(cfg4, {**p, **{k: p[k][part] for k in
                                            ("wi", "wg", "wo")}}, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        summed = summed + (y - shared)
    np.testing.assert_allclose(np.asarray(summed),
                               np.asarray(mla_moe.moe(whole.spec, lw, x)),
                               rtol=2e-5, atol=2e-5)


def test_dropless_when_every_token_picks_one_held_expert():
    """A router whose first choice is held expert 3 for every token: the
    program computes all T of those assignments, as the reference does."""
    from repro.models import moe

    c, pcfg, model, w = _setup(first=2, count=4)
    w = dict(w, router=w["router"].at[:, :, 3].add(40.0))
    lw, x = _layer_input(c, w, T=64)
    x = jnp.abs(x)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               model.to_program(w)["segments"][1]["ffn"])
    _, ids, _ = moe._route(pcfg, p, x)
    assert bool(jnp.all(ids[:, 0] == 3))
    y, _ = moe.apply_moe(pcfg, p, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(mla_moe.moe(model.spec, lw, x)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_then_decode_matches_the_full_forward(layout):
    """Chunked prefill of a 16-token prompt, then 8 tokens decoded through
    the latent cache, against the reference's full forward over the same
    24 tokens.  Float32 weights on the CPU: the program's absorbed path
    differs from the reference's only in the order of its sums, 1e-4 of a
    logit at most."""
    from repro.models import lm
    from repro.models.params import init_params

    c, pcfg, model, w = _setup()
    params = model.to_program(w)
    B, C, S, N = 2, 8, 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S + N), 0,
                                c["vocab_size"])
    want = _logits(model, w, tokens)
    paged = (B * 4, 8) if layout == "paged" else None
    cache = init_params(lm.make_cache(pcfg, B, 32, paged=paged),
                        jax.random.PRNGKey(0))
    extra = {}
    if paged:
        extra["page_table"] = jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
    for start in range(0, S, C):
        cache = lm.prefill_chunk(pcfg, params, {
            "tokens": tokens[:, start:start + C],
            "start": jnp.full((B,), start, jnp.int32), **extra}, cache)
    for t in range(S, S + N):
        logits, cache = lm.decode_step(pcfg, params, {
            "tokens": tokens[:, t:t + 1], "pos": jnp.full((B,), t, jnp.int32),
            **extra}, cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want[:, t]), rtol=1e-4,
                                   atol=1e-4, err_msg=f"t={t}")


# -- a whole run ----------------------------------------------------------------
@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_a_serving_run_is_correct_and_its_control_is_not(fresh_programs):
    """The cell's path at a small size: DecodeEngine over bf16 weights, the
    reference after the window.  The served tokens pass the cell's limit;
    the reference with every matrix rounded to float8 puts first tokens
    that fail it."""
    c = config(dtype="bfloat16", hidden_size=256, intermediate_size=512,
               moe_intermediate_size=128, num_hidden_layers=4,
               num_attention_heads=8, num_key_value_heads=8,
               kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, vocab_size=4096)
    cell = Cell("tiny.reason", 1, c, tiny.serve_mix("closed_loop"),
                "closed_loop",
                [{"name": n, "unit": "x"} for n in tiny.E2E["serve"]], [])
    import time
    out = run.serve_cell(cell, SEED, 1.5, None, time.perf_counter,
                         time.perf_counter(), 1, control=True)
    limit = c["limits"]["serve"]["served_gap"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["readings"]["served_gap"] <= limit, out["readings"]
    assert out["readings"]["control_gap"] > limit, out["readings"]


# -- the readers of the new scopes ----------------------------------------------
ATT = "jit(_fused_steps)/while/body/mla/mla_latent_attention/dot_general"
PRE = "jit(_prefill_chunk)/while/body/mla/mla_latent_attention/exp"
MOE = "jit(_fused_steps)/while/body/moe/moe_experts/ragged_dot"
# XLA's ragged dot on a TPU, as a profile names it: no scope of its own
RAGGED = "%ragged-dot-none = bf16[192,1408]{1,0} custom-call(%a, %b, %c)"


class _Run:
    pass


def _scoped_run(scopes=True):
    E = T.Event
    ops = [E("fusion.1", 0, 10), E("fusion.2", 10, 14), E("fusion.3", 14, 20),
           E("fusion.1", 40, 46), E(RAGGED, 46, 48), E(RAGGED + ".1", 48, 50),
           E("fusion.4", 60, 63), E("fusion.5", 63, 70), E(RAGGED, 70, 71)]
    mods = [E("jit__fused_steps(1)", 0, 20), E("jit__fused_steps(1)", 40, 50),
            E("jit__prefill_chunk(2)", 60, 70)]
    tr = T.Trace({"0": ops}, {"0": mods},
                 [E("chipbench.engine_step", 0, 52),
                  E("chipbench.engine_step", 55, 72)])
    r = _Run()
    r.trace, (r.t0, r.t1) = tr, tr.window("chipbench.engine_step")
    with open(FULL) as fh:
        r.model = spec.reference_model(json.load(fh))
    r.steps_per_sync, r.chips = 2, 1
    r.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    rec = type("Rec", (), {})()
    rec.decode = [(99, 2), (9, 1)]          # kv_len 100, 101 and 10
    r.records = [rec]
    r.program = P.ProgramTrace(scopes={"0": {
        "fusion.1": ATT, "fusion.2": MOE, "fusion.3": "jit(_fused_steps)/head",
        "fusion.4": PRE, "fusion.5": MOE, RAGGED: "ragged-dot-none"}}
        if scopes else {})
    return r


def test_scope_readers_by_hand():
    r = _scoped_run()
    # latent attention in decode: 10 + 6 ns over kv_len 100, 101, 10
    ops = sum(r.model.mla_decode_work(n)[0] for n in (100, 101, 10))
    nbytes = sum(r.model.mla_decode_work(n)[1] for n in (100, 101, 10))
    t = max(27 * ops / 197e12, 27 * nbytes / 819e9)
    assert metric_reader("mla_decode_roofline")(r) == pytest.approx(
        100 * t / 16e-9)
    # two decode calls of 2 steps, 20 and 10 ns
    assert metric_reader("decode_step_ms.reason")(r) == pytest.approx(7.5e-6)
    # under moe in two decode calls of 2 steps: 4 ns scoped and 4 ns of
    # ragged dots (one known by its stack, one by its name alone); the
    # prefill's are not counted
    assert metric_reader("moe_ms_per_decode_step")(r) == pytest.approx(2e-6)


@pytest.mark.parametrize("scopes", [True, False])
def test_scope_readers_find_nothing_without_scopes_or_programs(scopes):
    """A program without the scopes (the one before them) or a window with
    none of the step programs: the readers return nothing."""
    r = _scoped_run(scopes)
    if scopes:
        r.trace = T.Trace({"0": r.trace.ops["0"]}, {"0": []}, r.trace.spans)
    else:
        r.trace = T.Trace({"0": [o for o in r.trace.ops["0"]
                                 if "ragged" not in o.name]},
                          r.trace.modules, r.trace.spans)
    for name in ("mla_decode_roofline", "moe_ms_per_decode_step"):
        assert metric_reader(name)(r) is None, name
