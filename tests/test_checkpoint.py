"""Checkpointer: atomic save/restore with bf16, async writes, pruning,
restore-onto-different-sharding (elastic restart)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ck


def tree():
    return {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16) * 1.5,
                   "c": jnp.zeros((), jnp.int32)},
        "lst": [jnp.full((2,), 7, jnp.int8)],
    }


def test_roundtrip_including_bf16(tmp_path):
    t = tree()
    ck.save(str(tmp_path), 5, t)
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    restored, step, meta = ck.restore(str(tmp_path), like)
    assert step == 5
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a, np.float32),
                                                   np.asarray(b, np.float32)),
        t, restored)
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_async_write_and_prune(tmp_path):
    t = tree()
    for s in (1, 2, 3, 4):
        w = ck.save(str(tmp_path), s, t, async_write=True)
        w.join()
    ck.prune(str(tmp_path), keep=2)
    assert ck.available_steps(str(tmp_path)) == [3, 4]


def test_restore_latest_by_default(tmp_path):
    t = tree()
    ck.save(str(tmp_path), 1, t)
    ck.save(str(tmp_path), 9, jax.tree_util.tree_map(lambda x: x + 1, t))
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    _, step, _ = ck.restore(str(tmp_path), like)
    assert step == 9


RESHARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import checkpointer as ck
    from repro.launch.mesh import make_mesh

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    # save from a 4-way model sharding
    mesh1 = make_mesh((4,), ("model",))
    sh1 = {"w": NamedSharding(mesh1, P("model", None))}
    t1 = jax.tree_util.tree_map(jax.device_put, tree, sh1)
    ck.save("@DIR@", 1, t1)

    # restore onto a DIFFERENT mesh (2x2) and sharding (elastic restart)
    mesh2 = make_mesh((2, 2), ("data", "model"))
    sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    restored, step, _ = ck.restore("@DIR@", like, shardings=sh2)
    assert restored["w"].sharding == sh2["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    print("RESHARD_OK")
""")


def test_elastic_reshard_restore_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", RESHARD.replace("@DIR@", str(tmp_path))],
        capture_output=True, text=True, env=env, timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "RESHARD_OK" in r.stdout


def test_train_loop_resumes_after_injected_failure(tmp_path):
    from repro.configs import reduced_config
    from repro.data.synthetic import data_config_for
    from repro.train.loop import TrainJob, run_training

    cfg = reduced_config("smollm-360m")
    dc = data_config_for(cfg, seq_len=32, batch_size=2)
    job = TrainJob(total_steps=20, ckpt_every=5, ckpt_dir=str(tmp_path),
                   log_every=5, warmup=2, fail_after_step=11,
                   async_ckpt=False)
    with pytest.raises(RuntimeError, match="injected failure"):
        run_training(cfg, dc, job, log=lambda *a: None)
    assert max(ck.available_steps(str(tmp_path))) >= 10
    # restart (same arguments, as the ExpoCloud worker would re-run it)
    job2 = TrainJob(total_steps=20, ckpt_every=5, ckpt_dir=str(tmp_path),
                    log_every=5, warmup=2, async_ckpt=False)
    hist, final, _ = run_training(cfg, dc, job2, log=lambda *a: None)
    assert final == 20
    assert ck.available_steps(str(tmp_path))[-1] == 20
