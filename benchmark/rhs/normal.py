"""Right-hand sides ``normal``: each one a field of independent standard
normal values, drawn afresh from the run's seed and its place in the ring,
so that every seed brings fields of its own."""

from __future__ import annotations

import numpy as np


def field(n: int, seed: np.random.SeedSequence, k: int, dtype) -> np.ndarray:
    """The ``k``-th right-hand side of the ring, ``n`` values."""
    child = np.random.SeedSequence(seed.entropy,
                                   spawn_key=seed.spawn_key + (k,))
    rng = np.random.default_rng(child)
    return rng.standard_normal(n, dtype=np.float32).astype(dtype, copy=False)
