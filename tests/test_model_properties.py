"""Property tests on model invariants (hypothesis + targeted)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs import ARCH_IDS, get_config, get_shape, reduced_config
from repro.configs.analysis import hardness_tuple, model_flops, param_counts
from repro.models.rope import apply_rope


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def test_rope_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32))
    r = apply_rope(x, jnp.arange(8), theta=10000.0)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(r, axis=-1), rtol=1e-5)


def test_rope_relative_position_property():
    """<R(p)q, R(p+d)k> depends only on d (the RoPE defining property)."""
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 32))
    def dot_at(p, d):
        rq = apply_rope(q, jnp.array([p]), theta=100.0)
        rk = apply_rope(k, jnp.array([p + d]), theta=100.0)
        return float(jnp.sum(rq * rk))
    for d in (0, 3, 7):
        vals = [dot_at(p, d) for p in (0, 5, 11)]
        np.testing.assert_allclose(vals, vals[0], rtol=1e-4, atol=1e-4)


def test_rope_partial_keeps_tail_identity():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 2, 32))
    r = apply_rope(x, jnp.arange(4), rotary_dim=16)
    np.testing.assert_array_equal(np.asarray(r[..., 16:]),
                                  np.asarray(x[..., 16:]))


def test_rope_interleaved_differs_from_half_split():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 1, 16))
    a = apply_rope(x, jnp.arange(4), interleaved=False)
    b = apply_rope(x, jnp.arange(4), interleaved=True)
    assert not np.allclose(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# causality
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m"])
def test_causality_future_tokens_do_not_affect_past(arch):
    """Changing token t must not change logits at positions < t."""
    from repro.models import lm
    from repro.models.params import init_params

    cfg = reduced_config(arch).replace(dtype="float32")
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    S = 12
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                             cfg.vocab_size)
    tok2 = tok.at[0, S - 1].set((tok[0, S - 1] + 7) % cfg.vocab_size)

    def logits_fn(t):
        h = lm.embed_tokens(cfg, params, t)
        h, _, _ = lm.backbone(cfg, params, h, jnp.arange(S)[None, :],
                              remat=False)
        from repro.models.layers import rmsnorm
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return lm.apply_head(cfg, params, h)

    l1, l2 = logits_fn(tok), logits_fn(tok2)
    np.testing.assert_allclose(np.asarray(l1[:, :S - 1]),
                               np.asarray(l2[:, :S - 1]), atol=1e-4)
    assert not np.allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]))


# ---------------------------------------------------------------------------
# static analysis (hardness) properties
# ---------------------------------------------------------------------------
def test_hardness_monotone_in_shape():
    """Bigger shapes must dominate smaller ones for the same arch —
    required for domino pruning across the exploration grid."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        t4 = hardness_tuple(cfg, get_shape("train_4k"))
        # train_4k vs itself with a bigger synthetic batch
        from repro.configs.shapes import ShapeConfig
        bigger = ShapeConfig("x", 4096, 512, "train")
        tb = hardness_tuple(cfg, bigger)
        assert all(b >= a for a, b in zip(t4, tb, strict=True)), arch


def test_model_flops_scale_with_tokens():
    cfg = get_config("qwen3-4b")
    from repro.configs.shapes import ShapeConfig
    f1 = model_flops(cfg, ShapeConfig("a", 1024, 8, "train"))
    f2 = model_flops(cfg, ShapeConfig("b", 1024, 16, "train"))
    assert abs(f2 / f1 - 2.0) < 0.01


def test_moe_active_params_much_smaller_than_total():
    pc = param_counts(get_config("deepseek-v3-671b"))
    assert pc.active < pc.total / 10


@given(st.integers(0, 7), st.integers(1, 8), st.integers(1, 40),
       st.integers(0, 2 ** 16))
@settings(max_examples=20, deadline=None)
def test_dispatch_computes_every_held_assignment(first, count, T, seed):
    """Whatever the routing and the held range, each token's output is the
    sum over its assignments to held experts of weight times that expert's
    FFN: nothing is dropped, nothing held elsewhere is added."""
    import dataclasses

    from repro.models.moe import _dispatch_combine, _route, make_moe
    from repro.models.params import init_params

    cfg = reduced_config("olmoe-1b-7b").replace(dtype="float32")
    count = min(count, cfg.moe.num_experts - first)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held_first=first,
                                              held_count=count))
    p = init_params(make_moe(cfg), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, cfg.d_model))
    w, ids, _ = _route(cfg, p, x)
    got = _dispatch_combine(cfg, p, x, w, ids, first=first, count=count)
    want = np.zeros((T, cfg.d_model), np.float32)
    for t in range(T):
        for j in range(cfg.moe.top_k):
            e = int(ids[t, j]) - first
            if 0 <= e < count:
                h = jax.nn.silu(x[t] @ p["wg"][e]) * (x[t] @ p["wi"][e])
                want[t] += float(w[t, j]) * np.asarray(h @ p["wo"][e])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
