"""MoE dispatch/combine Pallas TPU kernels.

The GShard capacity-dispatch einsum multiplies a (g × E·C) one-hot matrix
per token group — O(E·C) work and memory per token (3072 slots/token at
kimi-k2 dims; §Perf backlog). On TPU the dispatch is really a GATHER:
slot (e, c) copies token row ``idx[e, c]``. These kernels implement that
directly: the dispatch gathers token rows into expert slots via VMEM
dynamic slices, and the combine gathers expert outputs back per (token,
choice) pair and accumulates with the gate weights — O(k) per token.

Grid: one program per (group, expert-block); rows move HBM→VMEM once.
Validated in interpret mode against the einsum reference (ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(idx_ref, x_ref, out_ref, *, C: int):
    """idx: (1, blkE, C) int32 token ids (-1 = empty slot);
    x: (1, g, d); out: (1, blkE, C, d)."""
    blkE = idx_ref.shape[1]
    d = x_ref.shape[-1]
    for e in range(blkE):          # static unroll: blkE × C dynamic slices
        for c in range(C):
            t = idx_ref[0, e, c]
            valid = t >= 0
            row = x_ref[0, pl.ds(jnp.maximum(t, 0), 1), :]     # (1, d)
            out_ref[0, e, c, :] = jnp.where(valid, row[0],
                                            jnp.zeros((d,), out_ref.dtype))


def _combine_kernel(idx_ref, gates_ref, eout_ref, out_ref, *, k: int):
    """idx: (1, g, k) int32 flat slot ids into (E*C); gates: (1, g, k);
    eout: (1, E, C, d) expert outputs; out: (1, g, d)."""
    g = idx_ref.shape[1]
    E, C, d = eout_ref.shape[1], eout_ref.shape[2], eout_ref.shape[3]
    flat = eout_ref[0].reshape(E * C, d)
    for t in range(g):             # static unroll over tokens in the group
        acc = jnp.zeros((d,), jnp.float32)
        for j in range(k):
            s = idx_ref[0, t, j]
            valid = s >= 0
            row = jax.lax.dynamic_slice(flat, (jnp.maximum(s, 0), 0), (1, d))
            acc = acc + jnp.where(valid,
                                  gates_ref[0, t, j] * row[0].astype(jnp.float32),
                                  0.0)
        out_ref[0, t, :] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_dispatch(idx, x, *, interpret: bool = False):
    """idx: (G, E, C) int32 token index per slot (-1 empty); x: (G, g, d).
    Returns expert inputs (G, E, C, d)."""
    G, E, C = idx.shape
    g, d = x.shape[1], x.shape[2]
    kernel = functools.partial(_dispatch_kernel, C=C)
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, E, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, g, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, E, C, d), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, E, C, d), x.dtype),
        interpret=interpret,
    )(idx, x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine(slot_idx, gates, expert_out, *, interpret: bool = False):
    """slot_idx: (G, g, k) flat (E*C) slot per (token, choice), -1 dropped;
    gates: (G, g, k) combine weights; expert_out: (G, E, C, d).
    Returns (G, g, d)."""
    G, g, k = slot_idx.shape
    E, C, d = expert_out.shape[1], expert_out.shape[2], expert_out.shape[3]
    kernel = functools.partial(_combine_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, g, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, g, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, E, C, d), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, g, d), jnp.float32),
        interpret=interpret,
    )(slot_idx, gates, expert_out)
