"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, ("data", "model").
    Multi-pod:  (2, 16, 16) = 512 chips, ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU-device tests (requires forced host devices)."""
    return _make_mesh(shape, axes)


def make_serve_mesh(dp: int = 0, *, model: int = 1):
    """Serving mesh: ``dp`` data shards x ``model`` tensor-parallel
    ranks over the first ``dp * model`` local devices (a mesh need not
    cover every device — the CI lane forces 8 host devices and shards
    4-wide). ``dp=0`` takes every device not claimed by ``model``.

    Prefill/decode disaggregation (``ServeEngine(prefill_shards=k)``)
    is a *logical* split of this mesh's data axis: prompt/chunk pages
    land on the first ``k`` shards' page subpools, decode slots on all
    shards read them cross-shard (see
    ``distributed.sharding.prefill_shard_ids``) — no separate mesh.

    On CPU, multi-device serving needs forced host devices, e.g.::

        XLA_FLAGS=--xla_force_host_platform_device_count=8
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if dp <= 0:
        dp = max(1, len(devs) // model)
    n = dp * model
    if n > len(devs):
        raise ValueError(
            f"serve mesh wants {dp}x{model}={n} devices, have "
            f"{len(devs)} (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return Mesh(np.asarray(devs[:n]).reshape(dp, model), ("data", "model"))
