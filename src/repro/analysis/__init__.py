"""expolint — AST-based invariant checker for the ExpoCloud core.

The fault-tolerance guarantees (backup takeover, at-least-once delivery,
trace replay) hold only while a handful of *conventions* hold:

  * ``SchedulerCore`` stays deterministic (no wall clock, no unseeded
    randomness, no environment reads) so snapshot -> restore -> replay is
    byte-identical,
  * every typed effect and protocol message has a handler on the primary,
    backup and client paths,
  * every mutable core field is covered by ``snapshot()``/``restore()``,
  * control broadcasts ride ``ctrl_seq``, never per-client ``srv_seq``
    (the PR-4 divergence bug),
  * Pallas kernels check grid divisibility.

``expolint`` turns those conventions into CI-enforced rules:

    PYTHONPATH=src python -m repro.analysis [--root DIR] [--json]

Per-line suppression: append ``# expolint: disable=<rule>`` to the
flagged line; ``# expolint: disable-file=<rule>`` anywhere in a file
suppresses the rule for the whole file.
"""
from __future__ import annotations

from repro.analysis.framework import (Project, Rule, Violation, all_rules,
                                      run_checks)

__all__ = ["Project", "Rule", "Violation", "all_rules", "run_checks"]
