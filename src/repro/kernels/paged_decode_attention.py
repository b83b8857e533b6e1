"""Paged flash-decode Pallas TPU kernel: one query token vs. KV *pages*.

The contiguous flash-decode kernel (``decode_attention.py``) streams a
dense ``(B, S, Hkv, hd)`` cache; its HBM traffic scales with
``slots × cache_len`` even when most slots hold short, early-stopped CAMD
candidates. This kernel instead reads KV through a **block table**: the
cache is a shared pool of ``(P, page_size, Hkv, hd)`` pages and each
batch row names its pages in ``block_table[b, i]``.

Mechanics: the grid is ``(B, n_blocks)``; a block is ``ppb`` consecutive
table entries (``_pages_per_block``: ``BLOCK_TOKENS`` tokens, so 16
pages at page size 16 and 2 at 128, fewer where the table is narrower).
The pools stay in HBM (``memory_space=ANY``) and the kernel copies each
block's pages itself (``make_async_copy``) into a double-buffered VMEM
scratch, starting the next live block's copies — the row's next block,
or the next row's first — before it computes the current one. Only
pages holding a live token (``page * ps < length``) are copied, and a
block that starts at or past the row's length is neither fetched nor
computed: HBM traffic and compute scale with the live tokens, not with
the table's width.

Scores: the ``(H, hd)`` queries meet the block flattened to
``(T·Hkv, hd)`` in one matmul, and a pair whose kv head is not the query
head's (GQA group ``h // G``), or whose token is past the length, is
masked. f32 scores, f32 running max/sum and f32 accumulator (an online
softmax across blocks); the output is written on the row's last grid
step.

**Quantized pools** (int8 / fp8-e4m3): per-row scale tensors
``k_scale``/``v_scale`` of shape (P, page_size, Hkv), viewed as one
``ps*Hkv``-lane row per page, are copied beside their pages (same
liveness test, same semaphores) and dequantize inside the kernel (K's
scale on the scores, V's on the probabilities), so the layout change
stays invisible above the kernel. HBM traffic per live token drops from
``2*hd*4`` bytes (fp32) to ``2*(hd + 4)`` (int8 values + one fp32 scale
per kv head).

On a TPU, Mosaic copies a page only if its minor dims fill whole tiles:
``hd`` a multiple of 128, ``Hkv`` a multiple of the dtype's sublane
packing (2 for bf16, 4 for int8/fp8), and ``ps*Hkv`` a multiple of 128
for the scale rows (page size 16 with 8 kv heads satisfies all three).
Interpret mode takes any shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens one grid step covers: enough to amortise a step's fixed cost,
# few enough that a row's last, partly live block wastes little compute
BLOCK_TOKENS = 256


def _pages_per_block(page_size: int, n_pages: int) -> int:
    return max(1, min(BLOCK_TOKENS // page_size, n_pages))


def validate_block_table(block_table, lengths, num_pages: int,
                         page_size: int):
    """Host-side guard against block-table corruption.

    The kernel (and the jnp oracle) clip page ids into [0, P-1], which
    turns an out-of-range id into wrong-but-plausible attention output.
    This check raises instead: every *live* entry (logical page i with
    ``i * page_size < lengths[b]``) must hold a page id in [0, P-1].
    Entries past the live length may point anywhere — they are never read.

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


def _paged_decode_kernel(len_ref, bt_ref, q_ref, *refs,
                         scale: float, ppb: int, n_pages: int,
                         quantized: bool):
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         sems, slot_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, slot_ref, m_scr, l_scr,
         acc_scr) = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_rows = pl.num_programs(0)
    n_blocks = pl.num_programs(1)
    ps, Hkv, hd = kbuf.shape[2:]
    T = ppb * ps
    H = q_ref.shape[1]
    G = H // Hkv

    def copies(row, blk, slot):
        """(live, descriptor) for every page copy of block ``blk`` of
        ``row`` into buffer ``slot``; a page past the length is dead."""
        length = len_ref[row]
        out = []
        for i in range(ppb):
            logical = blk * ppb + i
            live = logical * ps < length       # lengths <= n_pages * ps
            page = bt_ref[row * n_pages + jnp.minimum(logical, n_pages - 1)]
            pairs = [(k_hbm.at[page], kbuf.at[slot, i], 0),
                     (v_hbm.at[page], vbuf.at[slot, i], 1)]
            if quantized:
                # a page's ps*Hkv scales land at its columns of the row
                cols = pl.ds(i * ps * Hkv, ps * Hkv)
                pairs += [(ks_hbm.at[page], ksbuf.at[slot, :, cols], 0),
                          (vs_hbm.at[page], vsbuf.at[slot, :, cols], 1)]
            for src, dst, sem in pairs:
                out.append((live, pltpu.make_async_copy(
                    src, dst, sems.at[sem, slot])))
        return out

    def start(row, blk, slot):
        for live, cp in copies(row, blk, slot):
            pl.when(live)(cp.start)

    def wait(row, blk, slot):
        for live, cp in copies(row, blk, slot):
            pl.when(live)(cp.wait)

    @pl.when((b == 0) & (j == 0))
    def _prologue():
        # dead pages of a live block are never copied: zero the buffers
        # once so the masked tail of a block holds finite values
        for buf in (kbuf, vbuf) + ((ksbuf, vsbuf) if quantized else ()):
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * T < length)
    def _block():
        slot = slot_ref[0]
        # the next live block: this row's next one, else the next row's
        # first (every length is at least 1); its copies overlap this
        # block's compute
        stay = (j + 1) * T < length
        nb = jnp.where(stay, b, b + 1)
        nj = jnp.where(stay, j + 1, 0)

        @pl.when(nb < n_rows)
        def _():
            start(nb, nj, 1 - slot)

        wait(b, j, slot)
        k = kbuf[slot].astype(jnp.float32).reshape(T * Hkv, hd)
        v = vbuf[slot].astype(jnp.float32).reshape(T * Hkv, hd)
        q = q_ref[0].astype(jnp.float32)                      # (H, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                          # (H, T*Hkv)
        # column c holds token c // Hkv of the block, kv head c % Hkv
        col = jax.lax.broadcasted_iota(jnp.int32, (H, T * Hkv), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (H, T * Hkv), 0)
        valid = ((j * T + col // Hkv < length)
                 & (col % Hkv == row // G))
        if quantized:
            s = s * ksbuf[slot]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vsbuf[slot]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        slot_ref[0] = 1 - slot

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_call(q, k_pages, v_pages, block_table, lengths,
                       k_scale, v_scale, *, interpret: bool):
    B, _, H, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    n_pages = block_table.shape[1]
    ppb = _pages_per_block(ps, n_pages)
    n_blocks = pl.cdiv(n_pages, ppb)
    T = ppb * ps
    quantized = k_scale is not None
    bt = jnp.clip(block_table, 0, P - 1).astype(jnp.int32).reshape(-1)
    # a row of length 0 would leave the prefetched block unread: clamp
    ln = jnp.clip(lengths.astype(jnp.int32), 1, n_pages * ps)

    kernel = functools.partial(_paged_decode_kernel, scale=hd ** -0.5,
                               ppb=ppb, n_pages=n_pages,
                               quantized=quantized)
    row_spec = pl.BlockSpec((1, H, hd), lambda b, j, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [ln, bt, q[:, 0], k_pages, v_pages]
    scratch = [pltpu.VMEM((2, ppb, ps, Hkv, hd), k_pages.dtype),
               pltpu.VMEM((2, ppb, ps, Hkv, hd), v_pages.dtype)]
    if quantized:
        # (P, 1, ps*Hkv): a page's scales are one lane-dense row in the
        # order of the score columns (token-major, kv head minor); Mosaic
        # copies no (ps, Hkv) slab narrower than a 128-lane tile
        operands += [x.reshape(P, 1, ps * Hkv) for x in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, 1, T * Hkv), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),       # (K or V, buffer)
        pltpu.SMEM((1,), jnp.int32),           # buffer of this block
        pltpu.VMEM((H, 1), jnp.float32),
        pltpu.VMEM((H, 1), jnp.float32),
        pltpu.VMEM((H, hd), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,             # lengths, block table
            grid=(B, n_blocks),
            in_specs=[row_spec] + [hbm] * (len(operands) - 3),
            out_specs=row_spec,
            scratch_shapes=scratch,
        ),
        # one sequential walk: each step prefetches the next step's pages
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(*operands)
    return out.reshape(B, 1, H, hd)


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           k_scale=None, v_scale=None,
                           interpret: bool = False,
                           debug_validate: bool = False):
    """q: (B, 1, H, hd); k_pages/v_pages: (P, page_size, Hkv, hd);
    block_table: (B, n_pages) int32 page ids per row (entries past the
    live length may point anywhere — they are never read); lengths:
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
