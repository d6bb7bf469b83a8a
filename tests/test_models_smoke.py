"""Per-architecture smoke tests (assignment deliverable): instantiate the
REDUCED config of each family and run one forward/train step on CPU,
asserting output shapes and no NaNs.  Full configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.configs.analysis import param_counts
from repro.models import lm
from repro.models.params import init_params, param_count


def make_batch(cfg, B=2, S=64, rng=None):
    rng = rng or jax.random.PRNGKey(1)
    shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks else (B, S)
    tok = jax.random.randint(rng, shape, 0, cfg.vocab_size)
    batch = {"tokens": tok}
    if cfg.vision_stub:
        batch["image_embeds"] = jnp.zeros(
            (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
        batch["image_positions"] = jnp.tile(
            jnp.arange(cfg.num_image_tokens), (B, 1)).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    cfg = reduced_config(arch)
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss, metrics = jax.jit(lambda p, b: lm.train_loss(cfg, p, b))(
        params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch, float(loss))
    # gradient flows and is finite
    g = jax.grad(lambda p: lm.train_loss(cfg, p, batch)[0])(params)
    gn = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree_util.tree_leaves(g))
    assert bool(jnp.isfinite(gn)) and float(gn) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_prefill_and_decode(arch):
    cfg = reduced_config(arch)
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = make_batch(cfg, B, S)
    logits, pf_cache = jax.jit(lambda p, b: lm.prefill(cfg, p, b))(
        params, batch)
    if cfg.num_codebooks:
        assert logits.shape == (B, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    cache = init_params(lm.make_cache(cfg, B, S + 8), jax.random.PRNGKey(2))
    db = {"tokens": batch["tokens"][:, :1],
          "pos": jnp.zeros((B,), jnp.int32)}
    dlogits, new_cache = jax.jit(lambda p, b, c: lm.decode_step(cfg, p, b, c))(
        params, db, cache)
    assert bool(jnp.all(jnp.isfinite(dlogits.astype(jnp.float32))))
    # cache structure is preserved by the scan
    jax.tree_util.tree_map(lambda a, b: (a.shape, b.shape), cache, new_cache)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_descriptor_count_matches_analysis(arch):
    """The static analysis (used for hardness + MODEL_FLOPS) must agree
    with the actual parameter tree to within 2%."""
    from repro.configs import get_config

    cfg = get_config(arch)
    descr = lm.make_lm(cfg)
    actual = param_count(descr)
    predicted = param_counts(cfg).total
    assert abs(actual - predicted) / predicted < 0.02, \
        (arch, actual, predicted)


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m",
                                  "chatglm3-6b", "jamba-v0.1-52b",
                                  "deepseek-v2-lite"])
def test_decode_matches_teacher_forcing(arch):
    """Sequential decode with cache == full-sequence forward (the KV-cache /
    SSM-state correctness test), at fp32 tolerance."""
    cfg = reduced_config(arch).replace(dtype="float32")
    params = init_params(lm.make_lm(cfg), jax.random.PRNGKey(0))
    # run the equivalence in true fp32 (bf16 params would accumulate
    # ~5e-2 drift over the decode steps, masking real bugs)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)
    B, S = 1, 16
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                             cfg.vocab_size)
    # full forward logits at each position
    positions = jnp.arange(S)[None, :]
    h = lm.embed_tokens(cfg, params, tok)
    h, _, _ = lm.backbone(cfg, params, h, positions, remat=False)
    h = lm.apply_head(cfg, params,
                      jax.vmap(lambda x: x)(h))
    import repro.models.layers as L

    h_norm = L.rmsnorm(
        lm.backbone(cfg, params, lm.embed_tokens(cfg, params, tok),
                    positions, remat=False)[0],
        params["final_norm"], cfg.norm_eps)
    full_logits = lm.apply_head(cfg, params, h_norm)  # [B, S, V]

    cache = init_params(lm.make_cache(cfg, B, S), jax.random.PRNGKey(2))
    step = jax.jit(lambda p, b, c: lm.decode_step(cfg, p, b, c))
    for t in range(S):
        db = {"tokens": tok[:, t:t + 1],
              "pos": jnp.full((B,), t, jnp.int32)}
        dlogits, cache = step(params, db, cache)
        import numpy as np

        np.testing.assert_allclose(
            np.asarray(dlogits[0], np.float32),
            np.asarray(full_logits[0, t], np.float32),
            atol=2e-3, rtol=2e-3, err_msg=f"{arch} t={t}")
