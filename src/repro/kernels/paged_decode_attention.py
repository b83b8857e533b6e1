"""Paged flash-decode Pallas TPU kernel: one query token vs. KV *pages*.

The contiguous flash-decode kernel (``decode_attention.py``) streams a
dense ``(B, S, Hkv, hd)`` cache; its HBM traffic scales with
``slots × cache_len`` even when most slots hold short, early-stopped CAMD
candidates. This kernel instead reads KV through a **block table**: the
cache is a shared pool of ``(P, page_size, Hkv, hd)`` pages and each
batch row names its pages in ``block_table[b, i]``. HBM traffic scales
with *live* tokens — the roofline term that dominates decode.

Mechanics: the block table and per-row live lengths arrive as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``) so the page
index feeds the BlockSpec index map — the DMA engine fetches exactly the
page ``block_table[b, i]`` (all kv heads) for grid step ``(b, i)``.
Page-index is the minor-most grid dim; running max/sum/acc per kv head
live in VMEM scratch like the contiguous kernel, so fully-masked
trailing pages wash out of the online softmax (alpha underflows to 0
when a real max arrives; garbage from a masked-prefix page is erased
the same way).

GQA-aware like ``_decode_kernel``: the G query heads of one kv head form
the sublane dim of the score matmul, so each page is read once per
group, not once per query head.

**Quantized pools** (int8 / fp8-e4m3): when per-row scale tensors
``k_scale``/``v_scale`` of shape (P, page_size, Hkv) accompany the
pages, dequantization happens *inside* the kernel — the scale block
rides the same ``bt[b, i]`` index map as its page, one fp32 multiply
per (slot, head-dim) tile on the VPU, and the layout change stays
invisible above the kernel. HBM traffic per live token drops from
``2*hd*4`` bytes (fp32) to ``2*(hd + 4)`` (int8 values + one fp32
scale per kv head), a ~3.8x cut at hd=64.

The kernel's memory block size IS the page size — ``benchmarks/
autotune.py`` sweeps it (with the contiguous kernels' blk_q/blk_k/blk_s
and the engine's macro-step K) and ships the best configuration in
``BENCH_autotune.json``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def validate_block_table(block_table, lengths, num_pages: int,
                         page_size: int):
    """Host-side guard against block-table corruption.

    The kernel (and the jnp oracle) clip page ids into [0, P-1], which
    turns an out-of-range id into wrong-but-plausible attention output.
    This check raises instead: every *live* entry (logical page i with
    ``i * page_size < lengths[b]``) must hold a page id in [0, P-1].
    Entries past the live length may point anywhere — they are masked.

    Host-side by construction (``np.asarray`` on a tracer raises), so it
    runs in tests and interpret-mode harnesses, never inside a jitted
    serving step — pass ``debug_validate=True`` to the public entry
    points to enable it.
    """
    bt = np.asarray(block_table)
    ln = np.asarray(lengths)
    n = bt.shape[1]
    live = np.arange(n)[None, :] * page_size < ln[:, None]
    bad = live & ((bt < 0) | (bt >= num_pages))
    if bad.any():
        rows, cols = np.nonzero(bad)
        culprits = [(int(r), int(c), int(bt[r, c]))
                    for r, c in zip(rows[:8], cols[:8])]
        raise ValueError(
            f"block table references out-of-range page ids (pool has "
            f"{num_pages} pages): (row, logical_page, page_id) = "
            f"{culprits}" + (" ..." if len(rows) > 8 else ""))


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, page_size: int, n_pages: int,
                         quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    # token j of logical page i sits at absolute position i*ps + j; only
    # positions below the row's live length attend. (>=2D iota for TPU.)
    pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = pos < length                                   # (1, ps)
    for h in range(k_ref.shape[2]):        # static unroll over kv heads
        q = q_ref[0, h].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (ps, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quantized:
            # per-(slot, kv-head) fp32 scales: a (ps, 1) column
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = jnp.where(valid, s, NEG_INF)                   # (G, ps)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(p, v)
        m_scr[h] = m_new

    @pl.when(i == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_call(q, k_pages, v_pages, block_table, lengths,
                       k_scale, v_scale, *, interpret: bool):
    B, _, H, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    n_pages = block_table.shape[1]
    G = H // Hkv
    scale = hd ** -0.5
    quantized = k_scale is not None
    qg = q[:, 0].reshape(B, Hkv, G, hd)
    bt = jnp.clip(block_table, 0, P - 1).astype(jnp.int32)
    ln = lengths.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=ps, n_pages=n_pages,
                               quantized=quantized)
    # Each grid step moves one whole page, every kv head at once. Mosaic
    # tiles a block only if each of its last two dims is a multiple of
    # (8, 128) or spans the array: a one-head (1, hd) block does not,
    # the whole (Hkv, hd) page row does.
    page_spec = pl.BlockSpec((1, ps, Hkv, hd),
                             lambda b, i, bt, ln: (bt[b, i], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, Hkv, G, hd), lambda b, i, bt, ln: (b, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [bt, ln, qg, k_pages, v_pages]
    if quantized:
        # scale blocks ride the same block-table index map as their page
        scale_spec = pl.BlockSpec(
            (1, ps, Hkv), lambda b, i, bt, ln: (bt[b, i], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table + lengths
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, hd),
                               lambda b, i, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, 1, H, hd)


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           k_scale=None, v_scale=None,
                           interpret: bool = False,
                           debug_validate: bool = False):
    """q: (B, 1, H, hd); k_pages/v_pages: (P, page_size, Hkv, hd);
    block_table: (B, n_pages) int32 page ids per row (entries past the
    live length may point anywhere valid — they are masked); lengths:
    (B,) int32 live token count per row (>= 1).

    ``k_scale``/``v_scale``: optional (P, page_size, Hkv) float32
    per-row absmax scales for quantized (int8/fp8) pools — pass both or
    neither; dequantization happens inside the kernel.

    ``debug_validate``: host-side assert that every live block-table
    entry is in range (see ``validate_block_table``) instead of the
    silent clip — concrete (non-traced) inputs only.

    Returns (B, 1, H, hd).
    """
    assert (k_scale is None) == (v_scale is None)
    if debug_validate:
        validate_block_table(block_table, lengths, k_pages.shape[0],
                             k_pages.shape[1])
    return _paged_decode_call(q, k_pages, v_pages, block_table, lengths,
                              k_scale, v_scale, interpret=interpret)
