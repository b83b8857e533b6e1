"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax blockwise attention: grid (B, H, nQ, nK) with the kv axis
minor-most so each (b, h, q-block) accumulates across kv blocks through
VMEM scratch (running max / sum / output accumulator). Block shapes are
MXU-aligned (multiples of 128 on the lane dim, head_dim native).
Supports causal masking and sliding windows.

Target: TPU v5e. Validated against ``ref.flash_attention_ref`` in
interpret mode (CPU) across shape/dtype sweeps — see tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, blk_q: int, blk_k: int,
                  scale: float, nk: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale             # (blk_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)                     # (blk_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (blk_q, blk_k)

    q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    rel = q_pos - k_pos
    mask = k_pos < seq_len                                  # kv padding
    if causal:
        mask &= rel >= 0
    if window > 0:
        mask &= rel < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                     # (blk_q, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False):
    """q/k/v: (B, L, H, hd), heads already GQA-expanded. Returns (B, L, H, hd)."""
    B, L, H, hd = q.shape
    scale = hd ** -0.5
    pad = (-L) % blk_q
    padk = (-L) % blk_k
    # head-major inside the kernel: a (blk, hd) block of one head has
    # last two dims Mosaic can tile, which a (blk, 1, hd) slice of the
    # token-major layout does not
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if pad or padk:
        # pad q to blk_q and kv to blk_k multiples; padded kv masked in-kernel
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0)))
    Lq, Lk = q.shape[2], k.shape[2]
    nq, nk = Lq // blk_q, Lk // blk_k

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, blk_q=blk_q,
        blk_k=blk_k, scale=scale, nk=nk, seq_len=L)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :L].transpose(0, 2, 1, 3)
