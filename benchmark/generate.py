"""The general generator of a run's inputs, made from the seed.

The configuration names its operator (``benchmark/operators/<name>.py``,
``build(config, dtype)``); the traffic mix names its right-hand sides
(``benchmark/rhs/<name>.py``, ``field(n, seed, k, dtype)``) and how many the
ring holds.  A new operator or kind of right-hand side is a new file there.
"""

from __future__ import annotations

import importlib

import numpy as np
import scipy.sparse as sps


def operator(config: dict, dtype=np.float64) -> sps.csr_matrix:
    """The configuration's operator as a scipy CSR matrix."""
    kind = config["operator"]
    try:
        maker = importlib.import_module(f"benchmark.operators.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no generator for operator {kind!r}") from e
    return maker.build(config, dtype)


def seed_words(seed: int) -> np.random.SeedSequence:
    """Any whole number (negative, or past 64 bits) as a seed sequence."""
    seed = int(seed)
    words = [seed < 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return np.random.SeedSequence(words)


def _maker(traffic: dict):
    kind = traffic["rhs"]
    try:
        return importlib.import_module(f"benchmark.rhs.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no right-hand sides {kind!r}") from e


def rhs(n: int, traffic: dict, seed: int, k: int, dtype=np.float32):
    """The ``k``-th right-hand side of the run's ring, alone."""
    return _maker(traffic).field(n, seed_words(seed), k, dtype)


def rhs_ring(n: int, traffic: dict, seed: int, dtype=np.float32):
    """``(rhs_count, n)``: the whole ring, as the program is handed it."""
    maker, words = _maker(traffic), seed_words(seed)
    ring = np.empty((int(traffic["rhs_count"]), n), dtype)
    for k in range(ring.shape[0]):
        ring[k] = maker.field(n, words, k, dtype)
    return ring
