"""Sharding rules: logical->physical resolution, divisibility fallbacks,
ZeRO-1 state specs; multi-device parity via subprocess (host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P


def test_rules_resolution_and_divisibility(monkeypatch):
    # build rules without touching global device state: fake mesh-like
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))  # 1 real CPU device
    from repro.sharding.rules import make_rules

    rules = make_rules(mesh)
    # model axis size 1 divides everything
    assert rules.spec(("embed", "ffn"), (8, 16)) == P(None, "model")
    # unknown logical name -> replicated
    assert rules.spec(("nope",), (8,)) == P(None)


def test_zero1_spec_adds_dp_axis():
    from repro.sharding.rules import ShardingRules
    from repro.sharding.zero import zero1_spec

    class FakeMesh:          # avoids touching jax device state; data axis = 4
        axis_names = ("data",)
        shape = {"data": 4}

    rules = ShardingRules(mesh=FakeMesh(), table={})
    s = zero1_spec(P(None, "model"), (8, 16), rules)
    assert s == P("data", "model")
    # indivisible dim (7 % 4 != 0) -> unchanged
    s2 = zero1_spec(P(), (7,), rules)
    assert s2 == P()
    # first dim taken by 'model', second divisible -> data lands on dim 1
    s3 = zero1_spec(P("model"), (16, 8), rules)
    assert s3 == P("model", "data")


SUBPROCESS_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.models.params import init_params, param_shardings
    from repro.sharding.rules import make_rules, use_rules
    from repro.sharding.zero import opt_state_shardings
    from repro.train.optimizer import get_optimizer
    from repro.train.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = reduced_config("@ARCH@")
    descr = lm.make_lm(cfg)
    params = init_params(descr, jax.random.PRNGKey(0))
    opt = get_optimizer("adamw")
    state = opt.init(params)
    B, S = 4, 64
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tok}
    step_fn = make_train_step(cfg, opt, constant(1e-3))

    # single-device result
    p1, s1, m1 = jax.jit(step_fn)(params, state, batch, jnp.int32(0))
    loss1 = float(m1["loss"])

    # sharded result on a 2x4 mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh)
    psh = param_shardings(descr, rules)
    osh = opt_state_shardings("adamw", descr, rules, zero1=True)
    params_s = jax.tree_util.tree_map(jax.device_put, params, psh)
    state_s = jax.tree_util.tree_map(jax.device_put, state, osh)
    def wrapped(p, s, b, t):
        from repro.sharding.rules import use_rules as ur
        with ur(rules):
            return step_fn(p, s, b, t)
    with mesh:
        p2, s2, m2 = jax.jit(wrapped, in_shardings=(psh, osh, None, None),
                             out_shardings=(psh, osh, None))(
            params_s, state_s, batch, jnp.int32(0))
    loss2 = float(m2["loss"])
    assert abs(loss1 - loss2) < 5e-2, (loss1, loss2)
    # parameters after one step agree across the mesh boundary
    f1 = jax.tree_util.tree_leaves(p1)[0].astype(jnp.float32)
    f2 = jax.tree_util.tree_leaves(p2)[0].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               atol=3e-2, rtol=3e-2)
    print("PARITY_OK", loss1, loss2)
""")


@pytest.mark.parametrize("arch", ["smollm-360m", "olmoe-1b-7b"])
def test_sharded_train_step_parity_subprocess(arch):
    """One optimizer step on 1 device == on a 2x4 DPxTP mesh (8 host
    devices in a subprocess so this process keeps 1 device)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_PARITY.replace("@ARCH@", arch)],
        capture_output=True, text=True, env=env, timeout=480,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "PARITY_OK" in r.stdout


PER_SHARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS"] = "interpret"
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import make_rules, use_rules

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    n = lambda i, s: jax.random.normal(ks[i], s, jnp.float32)
    table = jax.random.permutation(ks[6], 16).astype(jnp.int32).reshape(4, 4)
    calls = {
        "flash": (lambda q, k, v: ops.flash_attention(q, k, v),
                  (n(0, (4, 32, 4, 16)), n(1, (4, 32, 2, 16)),
                   n(2, (4, 32, 2, 16)))),
        "decode": (lambda q, k, v: ops.decode_attention(
                       q, k, v, jnp.array([3, 9, 16, 30], jnp.int32)),
                   (n(3, (4, 4, 16)), n(4, (2, 4, 32, 16)),
                    n(5, (2, 4, 32, 16)))),
        "paged": (lambda q, k, v: ops.decode_attention_paged(
                      q, k, v, table, jnp.array([3, 9, 16, 30], jnp.int32)),
                  (n(3, (4, 4, 16)), n(4, (2, 16, 8, 16)),
                   n(5, (2, 16, 8, 16)))),
        "ssd": (lambda x, Bm, Cm: ops.ssd_scan(
                    x, jnp.full(x.shape[:3], 0.5), -jnp.ones(4), Bm, Cm,
                    chunk=16, return_final_state=True),
                (n(6, (4, 32, 4, 8)), n(7, (4, 32, 1, 8)),
                 n(1, (4, 32, 1, 8)))),
    }
    rules = make_rules(make_mesh((2, 2), ("data", "model")))
    for name, (fn, args) in calls.items():
        want = jax.jit(fn)(*args)
        def sharded(*a, fn=fn):
            with use_rules(rules):
                return fn(*a)
        got = jax.jit(sharded)(*args)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want), strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5, rtol=1e-5, err_msg=name)
    # the cache arrives sharded as models/attention.py declares it; with
    # K dividing the model axis no device gathers another's heads
    for name, logical in (("decode", ("kv_heads", "batch", "seq_kv", None)),
                          ("paged", ("kv_heads", None, "seq_kv", None))):
        fn, (q, k, v) = calls[name]
        put = lambda x: jax.device_put(x, rules.sharding(logical, x.shape))
        def sharded(*a, fn=fn):
            with use_rules(rules):
                return fn(*a)
        hlo = jax.jit(sharded).lower(q, put(k), put(v)).compile().as_text()
        assert "all-gather" not in hlo, name
    # the differentiable kernels take gradients per shard too
    q, k, v = calls["flash"][1]
    def loss(q, rules=None):
        with use_rules(rules):
            return jnp.sum(ops.flash_attention(q, k, v) ** 2)
    np.testing.assert_allclose(
        np.asarray(jax.jit(jax.grad(lambda q: loss(q, rules)))(q)),
        np.asarray(jax.jit(jax.grad(loss))(q)), atol=1e-4, rtol=1e-4)
    print("PER_SHARD_OK")
""")


def test_kernels_run_per_shard_subprocess():
    """Under sharding rules every Pallas kernel runs per shard inside
    shard_map (a Mosaic call cannot be partitioned by XLA) and matches the
    unsharded call; the decode kernels leave a head-sharded cache where
    it is."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", PER_SHARD],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "PER_SHARD_OK" in r.stdout
