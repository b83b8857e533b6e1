"""Flash-decode Pallas TPU kernel: one query token vs. a long KV cache.

Decode attention is pure HBM bandwidth — the kernel streams KV blocks
through VMEM once. GQA-aware: the query heads of one kv head form the
sublane dim of the score matmul (G × blk_s), so each kv block is read
ONCE per group instead of once per query head (cuts HBM traffic by
H/Hkv — the roofline term that dominates decode_32k).

Grid (B, nS) with the cache axis minor-most; each step loads every kv
head of one cache block, with running max/sum/acc per kv head in VMEM
scratch. ``kv_mask`` carries ring-buffer validity + window masking
computed by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, ns: int):
    isb = pl.program_id(1)

    @pl.when(isb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = mask_ref[0] > 0                                # (1, blk_s)
    for h in range(k_ref.shape[2]):        # static unroll over kv heads
        q = q_ref[0, h].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (blk_s, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = jnp.where(valid, s, NEG_INF)                   # (G, blk_s)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(p, v)
        m_scr[h] = m_new

    @pl.when(isb == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_s", "interpret"))
def decode_attention(q, k, v, kv_mask, *, blk_s: int = 256,
                     interpret: bool = False):
    """q: (B, 1, H, hd); k/v: (B, S, Hkv, hd); kv_mask: (B, S) bool.

    Returns (B, 1, H, hd).
    """
    B, _, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = hd ** -0.5
    pad = (-S) % blk_s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
    Sp = k.shape[1]
    ns = Sp // blk_s
    # group query heads by kv head: (B, Hkv, G, hd)
    qg = q[:, 0].reshape(B, Hkv, G, hd)
    # (B, 1, S): the mask block's last two dims are (1, blk_s)
    maskf = kv_mask.astype(jnp.float32)[:, None, :]

    kernel = functools.partial(_decode_kernel, scale=scale, ns=ns)
    # Each grid step moves a (blk_s, Hkv, hd) slab, every kv head at
    # once: the block's last two dims equal the cache's (Mosaic tiling).
    out = pl.pallas_call(
        kernel,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, blk_s, Hkv, hd), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, blk_s, Hkv, hd), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, blk_s), lambda b, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, hd), lambda b, s: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, maskf)
    return out.reshape(B, 1, H, hd)
