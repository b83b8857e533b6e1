"""Image ids that never repeat: request i carries image i, so every
image is encoded once and no image's pages are ever in the prefix
cache (the control for ``zipf``)."""
from __future__ import annotations

import numpy as np


def draw(spec: dict, n: int, rng) -> np.ndarray:
    return np.arange(n, dtype=np.int64)
