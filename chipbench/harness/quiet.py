"""Python's cyclic garbage collector kept out of a measured loop."""
from __future__ import annotations

import contextlib
import gc


@contextlib.contextmanager
def no_collection():
    """Collect once, freeze what set-up left (later collections never scan
    it), and keep the collector off until the loop ends: a collection over
    the many objects of a loaded model pauses the host for tens of ms."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()
