"""Lengths log-normal with ``median`` and ``sigma``, clipped to
[lo, hi]: the distribution's quantiles at (i + 1/2) / n, rounded, in an
order drawn from the seed."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def support(spec: dict) -> list:
    return list(range(spec["lo"], spec["hi"] + 1))


def draw(spec: dict, n: int, rng) -> np.ndarray:
    z = NormalDist()
    q = [z.inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * np.asarray(q))
    vals = np.clip(np.rint(vals), spec["lo"], spec["hi"]).astype(np.int64)
    return rng.permutation(vals)
