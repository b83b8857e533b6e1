"""Image ids from a pool of ``pool`` distinct images whose popularity
follows Zipf(``s``): the image of rank k is asked for in proportion to
k^-s. Every seed gets the same multiset of ids (each rank's count is its
share of ``n``, rounded by largest remainder), in an order drawn from
the seed; the pixels behind an id come from the seed (``images.py``)."""
from __future__ import annotations

import numpy as np


def counts(spec: dict, n: int) -> np.ndarray:
    """Requests per rank (rank 1 first), summing to ``n``."""
    w = np.arange(1, spec["pool"] + 1, dtype=np.float64) ** -spec["s"]
    exact = n * w / w.sum()
    c = np.floor(exact).astype(np.int64)
    short = n - int(c.sum())
    c[np.argsort(-(exact - c), kind="stable")[:short]] += 1
    return c


def draw(spec: dict, n: int, rng) -> np.ndarray:
    ids = np.repeat(np.arange(spec["pool"], dtype=np.int64), counts(spec, n))
    return rng.permutation(ids)
