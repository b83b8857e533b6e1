"""Arithmetic shared by the metric readers in ``metrics/``. Each reader
takes the run's context and returns a number, or None where the run
holds nothing to read (no trace, no such kernel, no such counter)."""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import costs


def window_tokens(ctx) -> int:
    """Every candidate's tokens produced in the window: those of the
    candidates finished by its close and of the live ones at its close,
    less the live ones' at its opening."""
    return ctx["counters"]["tokens"] - ctx["live0"]


def decode_tokens(ctx) -> int:
    """Tokens produced by decode steps in the window: the window's
    tokens less the first token of each candidate admitted in it, which
    admission samples from the prefill."""
    return window_tokens(ctx) - ctx["counters"]["sched"]["admitted_candidates"]


def _kernel_share(ctx, kernel: str, flops_bytes) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None or not flops_bytes:
        return None
    t_meas = tr["kernel_s"].get(kernel, 0.0)
    if t_meas <= 0:
        return None
    t_roof = sum(costs.roofline_seconds(f, b, ctx["peak"])[0]
                 for f, b in flops_bytes)
    return 100.0 * t_roof / t_meas


def paged_decode_roofline(ctx) -> Optional[float]:
    if ctx.get("trace") is None:
        return None
    s = ctx["sizes"]
    calls = [costs.paged_decode_call(s, c) for c in ctx["decode_ctxs"]
             if len(c)]
    calls = [(f * s["num_layers"], b * s["num_layers"]) for f, b in calls]
    return _kernel_share(ctx, "paged_decode", calls)


def mfu(ctx) -> Optional[float]:
    """Required model FLOPs of every prefill, decode step and image
    encode in the traced window, over its seconds times the peak."""
    if ctx.get("trace") is None:
        return None
    s = ctx["sizes"]
    flops = 0.0
    per_tok = costs.decode_token_flops(s, 0)
    pair = s["num_layers"] * costs.attention_pair_flops(s)
    for c in ctx["decode_ctxs"]:
        flops += per_tok * len(c) + pair * float(np.sum(c))
    for L in ctx["prefills"]:
        flops += costs.prefill_flops(s, L)
    # what the Recorder does not see, from the engine's counters over
    # the traced window: each image through the tower, and the tokens of
    # prefills it does not wrap (one-row, prefix-cache suffix, a chunked
    # prompt's last chunk), counted through the layers' matrices only
    d = ctx.get("trace_sched") or {}
    if "vision" in s:
        flops += d.get("image_encodes", 0) * costs.vision_encode_flops(s)
    rest = d.get("prefill_tokens", 0) - sum(ctx["prefills"])
    flops += 2 * s["num_layers"] * costs.layer_matmul_params(s) * max(rest, 0)
    return 100.0 * flops / (ctx["trace_s"] * ctx["peak"]["bf16_flops"])


def idle_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
