"""The ``train_2x2`` cell at tiny widths on four host devices, through
``run.py``'s training path: the ZeRO-1 step over a (data 2, model 2) mesh
against the reference spread over the four.  Sound, it is correct; with
the timed path broken underneath, ``correct`` is false.  The run needs a
process of its own, since the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from harness.spec import ROOT

CASES = ("sound", "unchanged", "half_batch", "dropped_shards")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join("chipbench", "tests", "mesh_cell.py"),
         *CASES], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["case"]: r for r in map(json.loads, p.stdout.splitlines())}


@pytest.mark.parametrize("case", CASES)
def test_train_2x2_on_four_host_devices(runs, case):
    r = runs[case]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["correct"] == (case == "sound"), r["checks"]
    if case == "dropped_shards":
        c = r["checks"]["change_gap"]
        assert c["value"] > c["limit"]
