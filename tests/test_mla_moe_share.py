"""DeepSeek-V2-Lite's mechanisms in the program: a held share of the
routed experts, dropless dispatch, queries with no compression, and YaRN
rotary frequencies with their softmax-scale correction."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import mla, moe
from repro.models.params import init_params
from repro.models.rope import (apply_rope, get_mscale, yarn_correction_range,
                               yarn_inv_freq)


def _cfg(**moe_kw):
    cfg = reduced_config("deepseek-v2-lite").replace(dtype="float32")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, **moe_kw))


def _x(cfg, T=48, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, cfg.d_model))


@pytest.mark.parametrize("shares", [2, 4])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts of ``shares`` layers whose held ranges cover every
    expert, plus the shared experts counted once, give the uncut layer."""
    whole = _cfg(held_first=0, held_count=0)
    E = whole.moe.num_experts
    p = init_params(moe.make_moe(whole), jax.random.PRNGKey(0))
    x = _x(whole)
    want, _ = moe.apply_moe(whole, p, x)
    shared = moe.apply_dense_ffn(whole, p["shared"], x)
    got = shared
    n = E // shares
    for i in range(shares):
        cfg = _cfg(held_first=i * n, held_count=n)
        part = dict(p, **{k: p[k][i * n:(i + 1) * n]
                          for k in ("wi", "wg", "wo")})
        y, _ = moe.apply_moe(cfg, part, x)
        got = got + (y - shared)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_drops_nothing_when_every_token_picks_one_expert():
    """A router that sends every token to held expert 1 first: all T
    assignments to it are computed (a capacity of T*k/E would drop most)."""
    cfg = _cfg(held_first=0, held_count=4)
    p = init_params(moe.make_moe(cfg), jax.random.PRNGKey(0))
    p["router"] = p["router"].at[:, 1].add(50.0)
    x = jnp.abs(_x(cfg, T=64))
    w, ids, _ = moe._route(cfg, p, x)
    assert bool(jnp.all(ids[:, 0] == 1))
    got = moe._dispatch_combine(cfg, p, x, w, ids, first=0, count=4)
    ffn = lambda e: (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wi"][e])) \
        @ p["wo"][e]
    want = sum(jnp.where((ids == e) & (e < 4), w, 0.0).sum(1, keepdims=True)
               * ffn(e) for e in range(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_reduced_preset_keeps_the_share_and_the_query_projection(
        monkeypatch):
    import repro.configs.registry as R

    full, cfg = get_config("deepseek-v2-lite"), reduced_config(
        "deepseek-v2-lite")
    assert full.mla.q_lora_rank == 0 and cfg.mla.q_lora_rank == 0
    assert reduced_config("deepseek-v3-671b").mla.q_lora_rank == 64
    assert (cfg.yarn_factor, cfg.rope_interleaved) == (40.0, True)
    assert full.moe.num_held == 64 and cfg.moe.num_held == 8
    names = set(mla.make_mla(cfg))
    assert "wq" in names and not names & {"wdq", "q_norm", "wuq"}
    share = full.replace(moe=dataclasses.replace(full.moe, held_first=8,
                                                 held_count=8))
    monkeypatch.setattr(R, "get_config", lambda name: share)
    small = R.reduced_config("deepseek-v2-lite")
    assert (small.moe.held_first, small.moe.num_held) == (0, 4)
    assert small.moe.num_experts == 8


def test_yarn_at_the_published_settings():
    """DeepSeek-V2-Lite: rope dim 64, theta 1e4, factor 40 over 4096
    positions, beta 32 / 1, mscale = mscale_all_dim = 0.707."""
    assert yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    assert get_mscale(40, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    cfg = get_config("deepseek-v2-lite")
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.5896,
                                                   rel=1e-4)
    got = yarn_inv_freq(64, 1e4, 40, 4096, 32, 1)
    for i in range(32):            # the published formula, term by term
        f_extra = 1e4 ** (-2 * i / 64)
        m = 1 - min(max((i - 10) / (23 - 10), 0), 1)
        assert got[i] == pytest.approx(f_extra / 40 * (1 - m) + f_extra * m,
                                       rel=1e-6)
    assert got[0] == pytest.approx(1.0) and got[31] == pytest.approx(
        1e4 ** (-62 / 64) / 40, rel=1e-6)


def test_yarn_factor_one_changes_nothing():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 64))
    pos = jnp.arange(5)[None]
    plain = apply_rope(x, pos, theta=1e4, interleaved=True)
    inv = yarn_inv_freq(64, 1e4, 1.0, 4096, 32, 1)
    same = apply_rope(x, pos, theta=1e4, interleaved=True, inv_freq=inv,
                      cos_scale=get_mscale(1.0, 0.707))
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain),
                               rtol=1e-6, atol=1e-6)
    assert get_mscale(1.0, 5.0) == 1.0
    cfg = get_config("deepseek-v3-671b")
    assert mla.softmax_scale(cfg) == 192 ** -0.5


def test_mla_rotates_interleaved_pairs_when_asked():
    """Rope pairs (2i, 2i+1) for the q and k rope parts: q·k of the rotated
    parts depends on positions only through their difference."""
    cfg = reduced_config("deepseek-v2-lite").replace(dtype="float32")
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    dot = lambda a, b: float(jnp.sum(mla._rope(cfg, q, jnp.array([[a]]))
                                     * mla._rope(cfg, k, jnp.array([[b]]))))
    assert dot(7, 3) == pytest.approx(dot(104, 100), rel=1e-4)
    pairs = mla._rope(cfg, q, jnp.array([[5]]))
    halves = mla._rope(cfg.replace(rope_interleaved=False), q,
                       jnp.array([[5]]))
    assert not np.allclose(np.asarray(pairs), np.asarray(halves))
    assert math.isclose(float(jnp.linalg.norm(pairs)),
                        float(jnp.linalg.norm(q)), rel_tol=1e-5)
