"""The seed of the benchmark's own weights. A family's ``make_weights``
(``benchmarks/families/<name>.py``) makes them on the device from ``--seed``
in one jitted call, in the type they are served in."""

from __future__ import annotations


def seed_words(seed: int):
    """Any whole number (the driver's seeds pass 2**31) as two uint32 words,
    to be passed to the jitted ``make_weights`` as an argument: a seed that
    is traced in as a constant makes another program, and another compile,
    of every seed."""
    import numpy as np

    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], dtype=np.uint32)
