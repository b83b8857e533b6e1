"""Kernel micro-benchmarks — name,us_per_call,derived CSV.

On CPU the Pallas kernels run against the jnp-reference path (interpret
mode is a correctness harness, not a perf one), so the numbers here time
the XLA oracle path; derived column reports achieved GFLOP/s. On a TPU
backend the same rows time the compiled kernels at the block sizes a
committed ``BENCH_autotune.json`` selected (``autotune.load_tuned``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

try:
    from benchmarks.autotune import load_tuned
except ImportError:          # invoked as a script: benchmarks/ is sys.path[0]
    from autotune import load_tuned
from repro.kernels import ops, ref
from repro.models.attention import kv_quantize


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(verbose: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)
    tuned = load_tuned()

    B, L, H, hd = 1, 1024, 4, 64
    q = jax.random.normal(key, (B, L, H, hd), jnp.float32)
    t_fa = tuned["flash_attention"]
    fa = jax.jit(lambda q: ops.flash_attention(
        q, q, q, causal=True, blk_q=t_fa["blk_q"], blk_k=t_fa["blk_k"]))
    us = _time(fa, q)
    flops = 4 * B * H * L * L * hd / 2  # causal half
    rows.append(("flash_attention_ref_1k", us, f"{flops/us/1e3:.1f}GFLOPs"))

    S, Hkv = 8192, 2
    qd = jax.random.normal(key, (B, 1, H, hd), jnp.float32)
    kd = jax.random.normal(key, (B, S, Hkv, hd), jnp.float32)
    mask = jnp.ones((B, S), bool)
    da = jax.jit(lambda q, k, m: ops.decode_attention(
        q, k, k, m, blk_s=tuned["decode_attention"]["blk_s"]))
    us = _time(da, qd, kd, mask)
    bytes_moved = 2 * B * S * Hkv * hd * 4
    rows.append(("decode_attention_ref_8k", us,
                 f"{bytes_moved/us/1e3:.1f}GBps"))

    # paged decode on the same 8k context, but only half the pages live —
    # the µs/token and bytes columns show paged traffic scaling with live
    # tokens where the contiguous row above pays slots × cache_len.
    ps = 128
    P = S // ps + 1
    live = S // 2
    n_pages = live // ps
    kp = jax.random.normal(key, (P, ps, Hkv, hd), jnp.float32)
    bt = (1 + jnp.arange(B * n_pages, dtype=jnp.int32)).reshape(B, n_pages)
    lengths = jnp.full((B,), live, jnp.int32)
    pda = jax.jit(lambda q, k, t, ln: ops.paged_decode_attention(
        q, k, k, t, ln))
    us = _time(pda, qd, kp, bt, lengths)
    bytes_moved = 2 * B * live * Hkv * hd * 4
    rows.append(("paged_decode_ref_8k_half_live", us,
                 f"{bytes_moved/us/1e3:.1f}GBps"))

    # same shape, int8 pool with in-kernel dequant: the bytes column is
    # what quantization buys — ~0.27x the fp32 traffic per live token.
    kq, ks = kv_quantize(kp, jnp.int8)
    pdq = jax.jit(lambda q, k, s, t, ln: ops.paged_decode_attention(
        q, k, k, t, ln, k_scale=s, v_scale=s))
    us = _time(pdq, qd, kq, ks, bt, lengths)
    bytes_moved = 2 * B * live * Hkv * (hd * 1 + 4)   # int8 values + scale
    rows.append(("paged_decode_int8_8k_half_live", us,
                 f"{bytes_moved/us/1e3:.1f}GBps"))

    Lx, Nv, Nt, d = 512, 256, 128, 256
    tok = jax.random.normal(key, (B, Lx, d))
    vis = jax.random.normal(key, (B, Nv, d))
    txt = jax.random.normal(key, (B, Nt, d))
    m = jnp.ones((B, Lx))
    xm = jax.jit(lambda t, m, v, x: ref.xmodal_score_ref(t, m, v, x))
    us = _time(xm, tok, m, vis, txt)
    flops = 2 * B * (Lx * Nv + Nt * Nv) * d
    rows.append(("xmodal_score_ref", us, f"{flops/us/1e3:.1f}GFLOPs"))

    if verbose:
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
