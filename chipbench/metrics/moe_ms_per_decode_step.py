"""Device time of the operations under the ``moe`` scope (routing,
dispatch, the held and shared experts, combine), and of XLA's ragged-dot
kernels that compute the held experts (they lose the scope), in the fused
decode programs, per decode step, in ms."""
from harness.readers import per_step_ms
from harness.scopes import scope_test


def read(run):
    test = scope_test(run, "moe", ragged_dot=True)
    ms = test and per_step_ms(run, test, "_fused_steps")
    return ms / run.steps_per_sync if ms else None
