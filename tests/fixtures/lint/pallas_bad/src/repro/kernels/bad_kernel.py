"""Fixture: an unchecked // grid."""
import jax.experimental.pallas as pl


def bad_kernel(x, block=128):
    S = x.shape[0]
    grid = (S // block,)
    return pl.pallas_call(lambda x_ref, o_ref: None, grid=grid)(x)
