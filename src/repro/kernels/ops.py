"""Public jit'd entry points for the Pallas kernels.

On a TPU these always dispatch the compiled Mosaic kernels. Everywhere
else (the CPU test runs) they return the jnp oracle, or run the kernels
in interpret mode when ``REPRO_KERNEL_MODE=interpret`` asks for it
(``REPRO_KERNEL_MODE`` in {"auto", "interpret", "ref"}; auto = oracle).
On a TPU any value but "auto" is an error: a run on the chip never
measures an oracle or the interpreter in place of the kernel.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_decode_attention as _pdec
from repro.kernels import ref as _ref
from repro.kernels import xmodal_score as _xm

_MODES = ("auto", "interpret", "ref")


def _mode() -> str:
    m = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if m not in _MODES:
        raise ValueError(f"REPRO_KERNEL_MODE must be one of {_MODES}, "
                         f"got {m!r}")
    if jax.devices()[0].platform == "tpu":
        if m != "auto":
            raise RuntimeError(
                f"REPRO_KERNEL_MODE={m!r} would replace the compiled "
                "Pallas kernel on a TPU; unset it")
        return "tpu"
    return "ref" if m == "auto" else m


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128):
    m = _mode()
    if m == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               blk_q=blk_q, blk_k=blk_k,
                               interpret=(m == "interpret"))


def decode_attention(q, k, v, kv_mask, *, blk_s: int = 256):
    m = _mode()
    if m == "ref":
        return _ref.decode_attention_ref(q, k, v, kv_mask)
    return _dec.decode_attention(q, k, v, kv_mask, blk_s=blk_s,
                                 interpret=(m == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           k_scale=None, v_scale=None,
                           debug_validate: bool = False):
    """``k_scale``/``v_scale``: per-row scales of quantized (int8/fp8)
    pools — both paths dequantize with them. ``debug_validate`` raises
    on out-of-range live page ids instead of silently clipping them
    (host-side — concrete inputs only, see ``validate_block_table``)."""
    if debug_validate:
        _pdec.validate_block_table(block_table, lengths,
                                   k_pages.shape[0], k_pages.shape[1])
    m = _mode()
    if m == "ref":
        return _ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                               block_table, lengths,
                                               k_scale=k_scale,
                                               v_scale=v_scale)
    return _pdec.paged_decode_attention(q, k_pages, v_pages, block_table,
                                        lengths, k_scale=k_scale,
                                        v_scale=v_scale,
                                        interpret=(m == "interpret"))


def xmodal_score(token_embs, mask, visual_feats, text_feats, *, blk: int = 128):
    m = _mode()
    if m == "ref":
        return _ref.xmodal_score_ref(token_embs, mask, visual_feats, text_feats)
    return _xm.xmodal_score(token_embs, mask, visual_feats, text_feats,
                            blk=blk, interpret=(m == "interpret"))
