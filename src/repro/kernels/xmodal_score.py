"""Fused cross-modal consistency scoring kernel (paper Eq. 8-9).

S_align needs two reductions over cosine-similarity matrices that are
never worth materializing at serving scale (L generated tokens × Nv
visual-evidence features, and Nt prompt tokens × Nv):

  term1 = mean_t mean_j cos(v_j, f(y_t))      (token ↔ visual grounding)
  term2 = mean_r max_j  cos(t_r, v_j)         (prompt ↔ visual consistency)

The kernel fuses L2 normalization, the block matmul, and the row
mean/max reductions; each (token-block × evidence-block) tile lives only
in VMEM. Outputs are per-batch scalar accumulators; the wrapper applies
the final 1/(L·Nv) and 1/Nt normalizations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _norm_rows(x, eps=1e-8):
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(n, eps)


def _sum2(x):
    """Sum of a 2-D tile as a (1, 1) tile — the accumulator's shape."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _mean_kernel(tok_ref, tmask_ref, vis_ref, vmask_ref, o_ref, acc_scr, *,
                 nl: int, nv: int):
    """Accumulates sum_t sum_j cos(tok_t, vis_j) over valid pairs."""
    il = pl.program_id(1)
    iv = pl.program_id(2)

    @pl.when((il == 0) & (iv == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    tok = _norm_rows(tok_ref[0].astype(jnp.float32))        # (blk_l, d)
    vis = _norm_rows(vis_ref[0].astype(jnp.float32))        # (blk_v, d)
    tm = tmask_ref[0]                                       # (blk_l, 1)
    vm = vmask_ref[0]                                       # (1, blk_v)
    sims = jax.lax.dot_general(tok, vis, (((1,), (1,)), ((), ())))
    acc_scr[...] += _sum2(sims * tm * vm)

    @pl.when((il == nl - 1) & (iv == nv - 1))
    def _finish():
        o_ref[0] = acc_scr[...]


def _max_kernel(txt_ref, tmask_ref, vis_ref, vmask_ref, o_ref, max_scr,
                acc_scr, *, nv: int, nt: int):
    """Accumulates sum_r max_j cos(txt_r, vis_j) over valid rows."""
    it = pl.program_id(1)
    iv = pl.program_id(2)

    @pl.when((it == 0) & (iv == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(iv == 0)
    def _row_init():
        max_scr[...] = jnp.full_like(max_scr, NEG_INF)

    txt = _norm_rows(txt_ref[0].astype(jnp.float32))        # (blk_t, d)
    vis = _norm_rows(vis_ref[0].astype(jnp.float32))        # (blk_v, d)
    vm = vmask_ref[0] > 0                                   # (1, blk_v)
    sims = jax.lax.dot_general(txt, vis, (((1,), (1,)), ((), ())))
    sims = jnp.where(vm, sims, NEG_INF)
    max_scr[...] = jnp.maximum(max_scr[...],
                               jnp.max(sims, axis=-1, keepdims=True))

    @pl.when(iv == nv - 1)
    def _row_finish():
        acc_scr[...] += _sum2(max_scr[...] * tmask_ref[0])

    @pl.when((it == nt - 1) & (iv == nv - 1))
    def _finish():
        o_ref[0] = acc_scr[...]


def _pad_to(x, n, axis):
    pad = (-x.shape[axis]) % n
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def xmodal_score(token_embs, mask, visual_feats, text_feats, *,
                 blk: int = 128, interpret: bool = False):
    """token_embs: (B, L, d); mask: (B, L); visual_feats: (B, Nv, d);
    text_feats: (B, Nt, d). Returns S_align (B,) per Eq. 9."""
    B, L, d = token_embs.shape
    Nv = visual_feats.shape[1]
    Nt = text_feats.shape[1]
    tok = _pad_to(token_embs, blk, 1)
    vis = _pad_to(visual_feats, blk, 1)
    txt = _pad_to(text_feats, blk, 1)
    # row masks (token, text) are (B, n, 1) columns and the visual mask a
    # (B, 1, n) row, so each mask block's last two dims tile on TPU and
    # broadcast against the (rows, visual) similarity tile directly
    tm = _pad_to(mask.astype(jnp.float32), blk, 1)[:, :, None]
    xm = _pad_to(jnp.ones((B, Nt), jnp.float32), blk, 1)[:, :, None]
    vm = _pad_to(jnp.ones((B, Nv), jnp.float32), blk, 1)[:, None, :]
    nl, nv, nt = tok.shape[1] // blk, vis.shape[1] // blk, txt.shape[1] // blk

    sum1 = pl.pallas_call(
        functools.partial(_mean_kernel, nl=nl, nv=nv),
        grid=(B, nl, nv),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda b, il, iv: (b, il, 0)),
            pl.BlockSpec((1, blk, 1), lambda b, il, iv: (b, il, 0)),
            pl.BlockSpec((1, blk, d), lambda b, il, iv: (b, iv, 0)),
            pl.BlockSpec((1, 1, blk), lambda b, il, iv: (b, 0, iv)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda b, il, iv: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(tok, tm, vis, vm)

    sum2 = pl.pallas_call(
        functools.partial(_max_kernel, nv=nv, nt=nt),
        grid=(B, nt, nv),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda b, it, iv: (b, it, 0)),
            pl.BlockSpec((1, blk, 1), lambda b, it, iv: (b, it, 0)),
            pl.BlockSpec((1, blk, d), lambda b, it, iv: (b, iv, 0)),
            pl.BlockSpec((1, 1, blk), lambda b, it, iv: (b, 0, iv)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda b, it, iv: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(txt, xm, vis, vm)

    n_tok = jnp.maximum(jnp.sum(mask.astype(jnp.float32), axis=-1), 1.0)
    term1 = sum1[:, 0, 0] / (n_tok * Nv)
    term2 = sum2[:, 0, 0] / Nt
    return 0.5 * (term1 + term2)
