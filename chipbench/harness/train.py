"""The training driver: the program's jitted ``make_train_step`` (AdamW,
remat as shipped, bfloat16) in a closed loop over seeded batches, on one
chip or over a mesh of chips.

Set-up builds one object, the compiled step with its state, and drives it
through its first ``check_steps`` steps on the window's own call and feed;
the comparison reads those steps.  The window then continues the same
object.  ``train_tok_s`` is the tokens of all steps completed in the
window over the window, which ends when the last step dispatched in it is
done.
"""
from __future__ import annotations

import contextlib
import sys

import jax
import jax.numpy as jnp

from harness import check, quiet

SPAN = "chipbench.train_step"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Trainer:
    def __init__(self, pcfg, model, mix: dict, params, batches):
        """``mix`` may lay the step over a mesh (``"mesh"``: {axis: size},
        ``"zero1"``): parameters and optimizer state are then placed by
        the program's sharding rules, ZeRO-1 spreading the optimizer state
        over the data axis, and each batch is split over the data axis."""
        from repro.train.optimizer import AdamW
        from repro.train.schedule import constant
        from repro.train.train_step import make_train_step

        self.mix, self.model = mix, model
        self.per_layer = check.per_layer_names(model)
        self.opt = AdamW()
        step = make_train_step(pcfg, self.opt, constant(mix["lr"]),
                               clip_norm=mix["clip_norm"], remat=mix["remat"])
        rows = [batches[i] for i in range(batches.shape[0])]
        if "mesh" in mix:
            step, placed, params, rows = self._on_mesh(pcfg, step, params,
                                                       rows)
        else:
            placed = {}
            self.opt_state = jax.jit(self.opt.init)(params)
        self.jstep = jax.jit(step, donate_argnums=(0, 1), **placed)
        self.params = params
        self.batches = rows
        self.i = 0

    def _on_mesh(self, pcfg, step, params, rows):
        """The step under the mesh's rules, its shardings, and the state and
        batches placed by them."""
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.models.params import param_shardings
        from repro.sharding.rules import make_rules, use_rules
        from repro.sharding.zero import opt_state_shardings

        axes = tuple(self.mix["mesh"])
        rules = make_rules(make_mesh(tuple(self.mix["mesh"][a] for a in axes),
                                     axes))
        descr = lm.make_lm(pcfg)
        p_sh = param_shardings(descr, rules)
        o_sh = opt_state_shardings("adamw", descr, rules,
                                   zero1=self.mix["zero1"])
        b_sh = rules.sharding(("batch", "seq"), rows[0].shape)
        params = jax.device_put(params, p_sh)
        self.opt_state = jax.jit(self.opt.init, out_shardings=o_sh)(params)
        rows = [jax.device_put(r, b_sh) for r in rows]

        def train_step(*a):         # the module name the readers look for
            with use_rules(rules):
                return step(*a)

        placed = {"in_shardings": (p_sh, o_sh, {"tokens": b_sh}, None),
                  "out_shardings": (p_sh, o_sh, None)}
        return train_step, placed, params, rows

    def step(self):
        b = self.batches[self.i % len(self.batches)]
        self.params, self.opt_state, m = self.jstep(
            self.params, self.opt_state, {"tokens": b}, jnp.int32(self.i))
        self.i += 1
        return m["loss"]

    def first_steps(self, to_ref, w0_fn) -> dict:
        """Run the first ``check_steps`` steps; read each loss, the first
        clipped gradient (from AdamW's first moment after one step), and the
        parameters' change after the last (from the float32 master copy the
        next step takes)."""
        losses, grad = [], None
        for _ in range(self.mix["check_steps"]):
            loss = self.step()
            if not bool(jnp.isfinite(loss)):    # the window's own check
                raise RuntimeError(f"step {self.i} lost its loss: {loss}")
            losses.append(float(loss))
            if grad is None:            # the moment's copy dies here
                grad = check.slice_norms(to_ref(jax.tree_util.tree_map(
                    lambda m: m / (1 - self.opt.b1), self.opt_state["m"])),
                    self.per_layer)
        w0 = w0_fn()
        master = to_ref(self.opt_state["master"])
        change = {}
        for k in master:                # one tensor's change live at a time
            change |= check.slice_norms(
                {k: master[k] - jax.device_put(w0[k], master[k].sharding)
                 .astype(jnp.float32)}, self.per_layer)
        del w0
        return {"losses": losses, "grad": grad, "change": change}

    def window(self, seconds: float, clock, trace_dir=None,
               trace_seconds: float = 0.0) -> dict:
        """Steps for ``seconds``; one step stays queued behind the one the
        host waits on."""
        with quiet.no_collection():
            return self._window(seconds, clock, trace_dir, trace_seconds)

    def _window(self, seconds, clock, trace_dir, trace_seconds) -> dict:
        tokens = self.mix["batch"] * self.mix["seq_len"]
        t0 = clock()
        done, bad, prev = 0, 0, None
        tracing = trace_dir is not None
        if tracing:
            jax.profiler.start_trace(trace_dir)
            trace_end = clock() + trace_seconds
        while True:
            with (jax.profiler.TraceAnnotation(SPAN) if tracing
                  else contextlib.nullcontext()):
                loss = self.step()
                if prev is not None:
                    bad += not bool(jnp.isfinite(prev))
                    done += 1
            prev = loss
            now = clock()
            if tracing and now >= trace_end:
                bad += not bool(jnp.isfinite(prev))
                done += 1
                prev = None
                jax.profiler.stop_trace()
                tracing = False
            if now - t0 >= seconds:
                break
        if prev is not None:
            bad += not bool(jnp.isfinite(prev))
            done += 1
        t1 = clock()
        log(f"window: {done} steps in {t1 - t0:.6f} s, {bad} not finite")
        return {"attempted": done, "failed": bad,
                "metrics": {"train_tok_s": done * tokens / (t1 - t0)}}
