"""Choosing operations by the scope they ran in: the ``tf_op`` stat that
``program_trace`` reads for each operation holds its name stack.  A scope
is one component of a name stack: ``moe`` holds ``.../moe/moe_experts/...``
but not ``.../moe_experts/...`` alone."""
from __future__ import annotations

import re

from harness.program_trace import of

# XLA's ragged dot on a TPU (the experts' matmuls, ``jax.lax.ragged_dot``)
# drops the name stack of the operation it replaces: its kernels are named
# ``ragged-dot-*`` alone, and only the expert layer uses them
RAGGED_DOT = "ragged-dot"


def in_scope(stack: str, scope: str) -> bool:
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", stack) \
        is not None


def scope_test(run, scope: str, ragged_dot: bool = False):
    """A test ``(device, event)`` for ``readers.per_step_ms``: the event ran
    under ``scope`` or, with ``ragged_dot``, is one of XLA's ragged-dot
    kernels.  None where the run recorded no name stacks."""
    pt = of(run)
    if pt is None:
        return None

    def test(d, e) -> bool:
        stack = pt.scopes.get(d, {}).get(e.name, "")
        return in_scope(stack, scope) or (ragged_dot and (
            stack.startswith(RAGGED_DOT)
            or e.name.lstrip("%").startswith(RAGGED_DOT)))
    return test
