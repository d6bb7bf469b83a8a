"""A tiny ``train_2x2`` cell on four host devices, sound and with the
timed path broken underneath; prints one JSON line per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chipbench/tests/mesh_cell.py sound unchanged half_batch \\
        dropped_shards
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import tiny  # noqa: E402

import run  # noqa: E402
from harness.spec import Cell  # noqa: E402

MIX = dict(tiny.TRAIN_MIX, mesh={"data": 2, "model": 2}, zero1=True)


def _keep_second_half(new, old):
    """``new`` with the second half of its last axis left at ``old``."""
    keep = jnp.arange(new.shape[-1]) < new.shape[-1] // 2
    return jnp.where(keep, new, old)


def _faulty(case):
    """The program's step with ``case`` planted in it."""
    def wrap(step):
        def faulty(params, opt_state, batch, i):
            if case == "half_batch":
                t = batch["tokens"]
                return step(params, opt_state, {"tokens": t[: len(t) // 2]},
                            i)
            new_p, new_o, metrics = step(params, opt_state, batch, i)
            if case == "unchanged":
                return params, opt_state, metrics
            # the exchange of one of two ZeRO-1 shards left out: half of
            # each tensor's update never reaches the parameters
            tm = jax.tree_util.tree_map
            return (tm(_keep_second_half, new_p, params),
                    dict(new_o, master=tm(_keep_second_half, new_o["master"],
                                          opt_state["master"])), metrics)
        return faulty
    return wrap


def main(cases):
    from repro.train import train_step

    real = train_step.make_train_step
    cell = Cell("tiny.train_2x2", 4, tiny.config(), MIX, "train_2x2",
                [{"name": n, "unit": "x"} for n in tiny.E2E["train"]], [])
    for case in cases:
        train_step.make_train_step = real if case == "sound" else (
            lambda *a, case=case, **k: _faulty(case)(real(*a, **k)))
        out = run.run_cell(cell, 2 ** 33 + 29, 1.5, False,
                           dict(tiny.CPU, count=4))
        print(json.dumps({"case": case, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "checks": out["checks"]}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
