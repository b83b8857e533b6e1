"""Seeded weights for the system under test, made by the benchmark.

The program is asked only for the *layout* of its parameters (the shapes
and dtypes of ``build_model``'s tree, traced abstractly, nothing
compiled or allocated); every value is drawn here from ``--seed``, on the
device, in one jitted call, in the dtype it is served in. The reference
reads the same arrays, so it takes nothing the program made.

Scales follow the usual initialisation of each kind of leaf: matrices
N(0, 1/d_in), embedding tables N(0, 1/d), norm scales 1, biases 0. A
configuration file may add rules of its own under ``init``: a leaf name
(or an ``fnmatch`` pattern over leaf names, such as
``vision/blocks/*/ls1``) mapped to ``{"normal": std}`` or
``{"const": value}``. The file's rules come before the built-in ones,
exact names before patterns; a leaf that no rule covers fails loudly.
"""
from __future__ import annotations

import fnmatch
from typing import Optional

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole number, including ones past 32 bits."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _rule(name: str, init: dict) -> Optional[dict]:
    if name in init:
        return init[name]
    return next((r for pat, r in init.items()
                 if fnmatch.fnmatchcase(name, pat)), None)


def _draw(key, name: str, shape, dtype, init: dict):
    rule = _rule(name, init)
    if rule is not None:
        (kind, val), = rule.items()
        if kind == "const":
            return jnp.full(shape, val, dtype)
        if kind == "normal":
            return (jax.random.normal(key, shape, jnp.float32)
                    * val).astype(dtype)
        raise ValueError(f"unknown initialisation rule {rule} for {name}")
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "q_norm", "k_norm"):
        return jnp.ones(shape, dtype)
    if last == "bias":
        return jnp.zeros(shape, dtype)
    if last == "table":
        std = shape[-1] ** -0.5
    elif last == "kernel":
        std = shape[-2] ** -0.5
    else:
        raise ValueError(f"no initialisation rule for parameter {name}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(shapes, seed: int, init: Optional[dict] = None):
    """Arrays shaped like ``shapes`` (a tree of ShapeDtypeStruct), drawn
    from ``seed`` in one jitted call, by the rules ``init`` (a
    configuration file's ``init`` group) and the built-in ones."""
    init = init or {}
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_leaf_name(p), s.shape, s.dtype) for p, s in flat]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(specs))
        return [_draw(k, n, sh, dt, init)
                for k, (n, sh, dt) in zip(keys, specs)]

    leaves = draw(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
