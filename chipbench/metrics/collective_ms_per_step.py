"""Device time of the collectives (all-gather, reduce-scatter, all-reduce,
collective-permute and all-to-all operations, their start and done halves
included) per train step the device finished in the traced window, in ms,
averaged over the chips.  None on one chip, which runs none."""
import re

from harness.readers import per_step_ms

COLLECTIVE = re.compile(
    r"(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)")


def read(run):
    return per_step_ms(run, lambda d, e: COLLECTIVE.search(
        e.name.partition(" = ")[0]) is not None)
