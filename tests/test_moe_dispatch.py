"""MoE dispatch placements: one device, gather (every token, the experts
split over the mesh) and ep (tokens split over data too) must agree;
routing properties."""
import os
import subprocess
import sys

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.moe import _route, make_moe
from repro.models.params import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(scoring="softmax"):
    cfg = reduced_config("olmoe-1b-7b")
    return cfg.replace(moe=dataclasses.replace(cfg.moe, scoring=scoring))


def test_route_topk_weights_normalised_sigmoid():
    cfg = _cfg(scoring="sigmoid")
    p = init_params(make_moe(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model),
                          jnp.bfloat16)
    w, ids, aux = _route(cfg, p, x)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, 1)), 1.0, atol=1e-5)
    assert ids.shape == (32, cfg.moe.top_k)
    assert bool(jnp.isfinite(aux))


def test_aux_loss_penalises_imbalance():
    cfg = _cfg()
    p = init_params(make_moe(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, cfg.d_model),
                          jnp.bfloat16)
    _, _, aux_balanced = _route(cfg, p, x)
    # collapse routing to expert 0
    p2 = dict(p)
    p2["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0)
    _, _, aux_collapsed = _route(cfg, p2, x)
    assert float(aux_collapsed) > float(aux_balanced)


EP_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.params import init_params, param_shardings
from repro.sharding.rules import make_rules, use_rules

cfg = reduced_config("olmoe-1b-7b")
descr = lm.make_lm(cfg)
params = init_params(descr, jax.random.PRNGKey(0))
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tok}
os.environ["REPRO_MOE"] = "gather"
ref, _ = jax.jit(lambda p, b: lm.train_loss(cfg, p, b, remat=False))(params, batch)
mesh = make_mesh((2, 4), ("data", "model"))
rules = make_rules(mesh)
psh = param_shardings(descr, rules)
ps = jax.tree_util.tree_map(jax.device_put, params, psh)
os.environ["REPRO_MOE"] = MODE
def f(p, b):
    with use_rules(rules):
        return lm.train_loss(cfg, p, b, remat=False)
with mesh:
    loss, _ = jax.jit(f, in_shardings=(psh, None))(ps, batch)
assert abs(float(loss) - float(ref)) < 2e-2, (float(loss), float(ref))
print("EP_PARITY_OK")
"""


def _parity(mode):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c",
                        f"MODE = {mode!r}\n" + EP_PARITY],
                       capture_output=True, text=True, env=env,
                       timeout=480, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "EP_PARITY_OK" in r.stdout


def test_ep_dispatch_parity_subprocess():
    _parity("ep")


def test_split_gather_parity_subprocess():
    """'gather' on a (data 2, model 4) mesh: each device computes the
    routed output of its quarter of the experts for every token, and the
    psum over 'model' matches one device."""
    _parity("gather")
