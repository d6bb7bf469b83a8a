"""Data pipeline determinism + optimizer correctness + schedules +
gradient compression (error feedback)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import DataConfig, SyntheticIterator, batch_at
from repro.train.optimizer import AdamW, Adafactor, clip_by_global_norm
from repro.train.schedule import warmup_cosine


def test_data_is_a_function_of_seed_and_step():
    cfg = DataConfig(vocab_size=100, seq_len=16, batch_size=4, seed=7)
    b1, b2 = batch_at(cfg, 3), batch_at(cfg, 3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_at(cfg, 4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    b4 = batch_at(DataConfig(100, 16, 4, seed=8), 3)
    assert not np.array_equal(b1["tokens"], b4["tokens"])


def test_iterator_restore_reproduces_stream():
    cfg = DataConfig(vocab_size=50, seq_len=8, batch_size=2)
    it = SyntheticIterator(cfg)
    first = [next(it)["tokens"] for _ in range(5)]
    state = it.state()
    later = [next(it)["tokens"] for _ in range(3)]
    it2 = SyntheticIterator(cfg)
    it2.restore(state)
    again = [next(it2)["tokens"] for _ in range(3)]
    for a, b in zip(later, again, strict=True):
        np.testing.assert_array_equal(a, b)


def test_data_codebooks_and_vlm_fields():
    cfg = DataConfig(vocab_size=64, seq_len=8, batch_size=2,
                     num_codebooks=4)
    assert batch_at(cfg, 0)["tokens"].shape == (2, 8, 4)
    cfg2 = DataConfig(vocab_size=64, seq_len=8, batch_size=2,
                      num_image_tokens=3, d_model=16)
    b = batch_at(cfg2, 0)
    assert b["image_embeds"].shape == (2, 3, 16)
    assert b["image_positions"].shape == (2, 3)


@pytest.mark.parametrize("opt", [AdamW(weight_decay=0.0), Adafactor()])
def test_optimizer_minimises_quadratic(opt):
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(300):
        g = jax.grad(loss_fn)(params)
        params, state = opt.update(g, state, params, lr=0.05)
    assert float(loss_fn(params)) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5
    assert float(norm) == pytest.approx(20.0)


def test_warmup_cosine_shape():
    lr = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-3)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-2)
    assert float(lr(55)) < float(lr(20))


def test_error_feedback_compression_preserves_signal():
    """int8 fake-quant with error feedback: the accumulated applied update
    converges to the accumulated true gradient (residual stays bounded)."""
    from repro.sharding.compression import make_error_feedback_compress

    init, transform = make_error_feedback_compress(None)
    g = {"w": jnp.array([0.001, -1.0, 0.5, 3.0])}
    residual = init(g)
    applied = jnp.zeros(4)
    for _ in range(50):
        cg, residual = transform(g, residual)
        applied = applied + cg["w"]
    # mean applied update ~ true gradient
    np.testing.assert_allclose(np.asarray(applied) / 50,
                               np.asarray(g["w"]), atol=2e-2)
    # residual bounded by one quantisation step's worth
    assert float(jnp.max(jnp.abs(residual["w"]))) < 0.05


def test_int8_allreduce_matches_mean_subprocess():
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.sharding.compression import allreduce_int8

        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

        f = jax.shard_map(lambda s: allreduce_int8(s, "data"), mesh=mesh,
                      in_specs=P("data", None), out_specs=P("data", None))
        out = f(x)
        want = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
        err = float(jnp.max(jnp.abs(out - want)))
        assert err < 0.05, err
        print("ALLREDUCE_OK", err)
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "ALLREDUCE_OK" in r.stdout
