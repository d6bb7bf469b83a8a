"""The training driver: the program's jitted ``make_train_step`` (AdamW,
remat as shipped, bfloat16) in a closed loop over seeded batches.

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

from harness import check

SPAN = "chipbench.train_step"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Trainer:
    def __init__(self, pcfg, spec, mix: dict, params, batches):
        from repro.train.optimizer import AdamW
        from repro.train.schedule import constant
        from repro.train.train_step import make_train_step

        self.mix, self.spec = mix, spec
        self.opt = AdamW()
        step = make_train_step(pcfg, self.opt, constant(mix["lr"]),
                               clip_norm=mix["clip_norm"], remat=mix["remat"])
        self.jstep = jax.jit(step, donate_argnums=(0, 1))
        self.params = params
        self.opt_state = jax.jit(self.opt.init)(params)
        self.batches = [batches[i] for i in range(batches.shape[0])]
        self.i = 0

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
            if grad is None:
                g = jax.tree_util.tree_map(lambda m: m / (1 - self.opt.b1),
                                           self.opt_state["m"])
                grad = check.slice_norms(to_ref(g), self.spec.layers)
        w0 = w0_fn()
        master = to_ref(self.opt_state["master"])
        change = check.slice_norms(
            {k: master[k] - w0[k].astype(jnp.float32) for k in master},
            self.spec.layers)
        del w0
        return {"losses": losses, "grad": grad, "change": change}

    def window(self, seconds: float, clock, trace_dir=None,
               trace_seconds: float = 0.0) -> dict:
        """Steps for ``seconds``; one step stays queued behind the one the
        host waits on."""
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
