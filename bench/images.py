"""Seeded images. A traffic mix with an ``images`` group gives each
request an image id (generator ``gen/<kind>.py``); the pixels of id
``i`` are drawn on demand from ``[seed, 3, i]``, float32 at the shape
the configuration file declares under ``sizes.vision``. The streams the
text path draws from (``[seed, 1]``, ``[seed, 2, i]``, ``[seed, 4]``)
are never touched: ids come from ``[seed, 5]``."""
from __future__ import annotations

import importlib

import numpy as np

# warm-up images take ids from here on: outside every pool, so the
# warm-up never encodes or caches an image the window asks for
WARM_ID0 = 1 << 40


def ids(spec: dict, n: int, seed: int) -> np.ndarray:
    kind = importlib.import_module(f"bench.gen.{spec['kind']}")
    out = kind.draw(spec, n, np.random.default_rng([seed, 5]))
    if len(out) != n or out.min() < 0 or out.max() >= WARM_ID0:
        raise ValueError(f"image ids of {spec} outside [0, {WARM_ID0})")
    return out


def pixels(seed: int, image_id: int, vision: dict) -> np.ndarray:
    r = np.random.default_rng([seed, 3, int(image_id)])
    return r.standard_normal(
        (vision["image_h"], vision["image_w"], vision["channels"]),
        dtype=np.float32)
