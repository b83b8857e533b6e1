"""Quantized paged-KV correctness.

Three layers of guarantee, mirroring ``test_paged_attention.py``'s
shape sweep (page sizes {16, 64, 128} x GQA group sizes):

1. quantize->dequantize roundtrip error is bounded by half the stored
   absmax scale (the int8 grid ULP) — property-tested with hypothesis
   when available, plus a deterministic seed sweep that always runs;
2. int8/fp8 paged decode attention (in-kernel dequant, interpret mode
   AND the jnp ref) matches the fp32 oracle within documented tolerance;
3. quantized cache writes land values AND scales at the block-table
   target, and the block-write equals sequential single writes.

Plus the ``debug_validate`` corruption path: out-of-range live page ids
raise instead of being silently clipped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import block_edge_lengths, nan_dead_pages
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                 validate_block_table)
from repro.models import attention as attn_lib
from repro.models.attention import (FP8_DTYPE, kv_dequantize, kv_quantize,
                                    kv_storage_dtype)

# int8 absmax on unit-normal data: per-element error <= scale/2 where
# scale = amax/127; attention output is a convex combination of v rows
# scaled by ~unit weights, so output error stays well under these.
INT8_TOLS = dict(rtol=6e-2, atol=6e-2)
# fp8-e4m3 has a 3-bit mantissa: relative error <= 2^-4 per element.
FP8_TOLS = dict(rtol=9e-2, atol=9e-2)

QDTYPES = [jnp.int8] + ([FP8_DTYPE] if FP8_DTYPE is not None else [])


def _setup(key, B, H, Hkv, hd, P, ps, n_pages, seed=0):
    q = jax.random.normal(key, (B, 1, H, hd))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (P, ps, Hkv, hd))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (P, ps, Hkv, hd))
    rng = np.random.default_rng(seed)
    perm = rng.permutation(P - 1) + 1          # page 0 = quarantine
    assert B * n_pages <= P - 1
    bt = jnp.asarray(perm[:B * n_pages].reshape(B, n_pages), jnp.int32)
    return q, kp, vp, bt


# ---------------------------------------------------------------------------
# 1. roundtrip bound
# ---------------------------------------------------------------------------

def _roundtrip_bound(x, qdtype):
    """|dequant(quant(x)) - x| <= half the quantization step, elementwise.

    int8: the grid step is exactly ``scale`` (absmax/127), so round-to-
    nearest lands within scale/2 (+ float slack). fp8-e4m3: the step at
    magnitude |y| is |y| * 2^-3, so the bound is |x|/16 + scale slack
    for the subnormal tail.
    """
    q, scale = kv_quantize(x, qdtype)
    err = jnp.abs(kv_dequantize(q, scale) - x.astype(jnp.float32))
    s = scale[..., None]
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        bound = 0.5 * s * (1 + 1e-5) + 1e-12
    else:
        bound = jnp.abs(x.astype(jnp.float32)) / 16.0 + s * 2e-2
    assert bool(jnp.all(err <= bound)), \
        f"max excess {float(jnp.max(err - bound)):.3e}"


@pytest.mark.parametrize("qdtype", QDTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("seed", range(8))
def test_roundtrip_bounded_by_scale(qdtype, seed):
    key = jax.random.PRNGKey(seed)
    scale_pow = jax.random.uniform(jax.random.fold_in(key, 1),
                                   (4, 8, 2, 1), minval=-6.0, maxval=6.0)
    x = jax.random.normal(key, (4, 8, 2, 16)) * 10.0 ** scale_pow
    _roundtrip_bound(x, qdtype)


def test_roundtrip_edge_cases():
    """Zeros, single-element spikes, and denormal-scale rows all stay
    in-bound (the scale floor keeps 0-rows exactly 0)."""
    x = jnp.zeros((2, 4, 1, 8))
    q, scale = kv_quantize(x, jnp.int8)
    assert bool(jnp.all(kv_dequantize(q, scale) == 0.0))
    spike = jnp.zeros((1, 1, 1, 8)).at[0, 0, 0, 3].set(1e4)
    _roundtrip_bound(spike, jnp.int8)
    _roundtrip_bound(jnp.full((1, 1, 1, 8), 1e-20), jnp.int8)


try:
    import hypothesis  # noqa: F401
    _HYP = True
except ImportError:
    _HYP = False

if _HYP:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 10 ** 6),
           rows=st.integers(1, 6), hd=st.sampled_from([4, 16, 64]),
           log_mag=st.floats(-8.0, 8.0),
           qi=st.integers(0, len(QDTYPES) - 1))
    def test_roundtrip_bounded_property(seed, rows, hd, log_mag, qi):
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (rows, 3, 2, hd)) * 10.0 ** log_mag
        _roundtrip_bound(x, QDTYPES[qi])


# ---------------------------------------------------------------------------
# 2. quantized paged decode vs fp32 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps", [16, 64, 128])
@pytest.mark.parametrize("Hkv,H", [(1, 4), (2, 8), (4, 4)])
def test_int8_paged_kernel_matches_fp32_oracle(ps, Hkv, H):
    B, hd, n_pages = 3, 64, 4
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps + H)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    lengths = jnp.asarray([1, (n_pages - 1) * ps + ps // 2 + 1, n_pages * ps],
                          jnp.int32)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    kq, ks = kv_quantize(kp, jnp.int8)
    vq, vs = kv_quantize(vp, jnp.int8)
    for out in (
        paged_decode_attention(q, kq, vq, bt, lengths, k_scale=ks,
                               v_scale=vs, interpret=True),
        ref.paged_decode_attention_ref(q, kq, vq, bt, lengths, k_scale=ks,
                                       v_scale=vs),
    ):
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   **INT8_TOLS)


@pytest.mark.skipif(FP8_DTYPE is None,
                    reason="jax build lacks float8_e4m3fn")
@pytest.mark.parametrize("ps", [16, 64])
def test_fp8_paged_kernel_matches_fp32_oracle(ps):
    B, H, Hkv, hd, n_pages = 3, 8, 2, 64, 4
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    lengths = jnp.asarray([1, (n_pages - 1) * ps + ps // 2 + 1, n_pages * ps],
                          jnp.int32)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    kq, ks = kv_quantize(kp, FP8_DTYPE)
    vq, vs = kv_quantize(vp, FP8_DTYPE)
    out = paged_decode_attention(q, kq, vq, bt, lengths, k_scale=ks,
                                 v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **FP8_TOLS)


@pytest.mark.parametrize("qdtype", QDTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("ps,n_pages", [(16, 21), (64, 5)])
def test_quantized_paged_kernel_multi_block(qdtype, ps, n_pages):
    """Several kernel blocks per row, lengths at the block edges, and
    NaN scales on every page no live entry names: the scales ride the
    pages' copies, so a finite output equal to the clean pool's proves
    no dead scale page was read either."""
    B, H, Hkv, hd = 5, 8, 2, 64
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps + n_pages)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    lengths = block_edge_lengths(ps, n_pages)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    kq, ks = kv_quantize(kp, qdtype)
    vq, vs = kv_quantize(vp, qdtype)
    clean = ref.paged_decode_attention_ref(q, kq, vq, bt, lengths,
                                           k_scale=ks, v_scale=vs)
    out = paged_decode_attention(q, kq, vq, bt, lengths,
                                 k_scale=nan_dead_pages(ks, bt, lengths, ps),
                                 v_scale=nan_dead_pages(vs, bt, lengths, ps),
                                 interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(clean),
                               rtol=2e-5, atol=2e-5)
    tols = INT8_TOLS if jnp.dtype(qdtype) == jnp.dtype(jnp.int8) else FP8_TOLS
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **tols)


def test_ops_dispatch_passes_scales(monkeypatch):
    """ops.paged_decode_attention must dequantize in BOTH kernel modes —
    a ref/interpret result without scales would be garbage-scaled."""
    B, H, Hkv, hd, ps, n_pages = 2, 4, 2, 32, 16, 2
    P = B * n_pages + 1
    key = jax.random.PRNGKey(3)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    # make scale-dropping visible: blow V magnitudes up 100x (output is
    # linear in v, so missing dequant is a ~100x error; scaling k would
    # sharpen softmax into an argmax and make the check brittle instead)
    vp = vp * 100.0
    lengths = jnp.asarray([ps + 1, 2 * ps], jnp.int32)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    kq, ks = kv_quantize(kp, jnp.int8)
    vq, vs = kv_quantize(vp, jnp.int8)
    for mode in ("ref", "interpret"):
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
        out = ops.paged_decode_attention(q, kq, vq, bt, lengths,
                                         k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=6e-2, atol=6.0)  # atol ~ 100x scale


# ---------------------------------------------------------------------------
# 3. quantized cache writes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps", [16, 64])
def test_quantized_cache_write_layout(ps):
    """Quantized paged_cache_write lands dequantizable values at
    bt[b, p//ps] offset p%ps, scales alongside, idle rows quarantine."""
    cfg = get_config("qwen3-0.6b").reduced()
    P = 8
    cache = attn_lib.make_paged_kv_cache(cfg, P, ps, jnp.float32,
                                         kv_dtype="int8")
    assert set(cache) == {"k_pages", "v_pages", "k_scale", "v_scale"}
    assert cache["k_pages"].dtype == jnp.int8
    assert cache["k_scale"].shape == (P, ps, cfg.num_kv_heads)
    B = 3
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0], [0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([ps + 3, 2 * ps - 1, 10 ** 6], jnp.int32)
    k_new = jax.random.normal(jax.random.PRNGKey(0), (B, 1, Hkv, hd))
    new = attn_lib.paged_cache_write(cache, k_new, k_new + 1.0, pos, bt)
    deq_k = kv_dequantize(new["k_pages"], new["k_scale"])
    deq_v = kv_dequantize(new["v_pages"], new["v_scale"])
    np.testing.assert_allclose(np.asarray(deq_k[2, 3]),
                               np.asarray(k_new[0, 0]), **INT8_TOLS)
    np.testing.assert_allclose(np.asarray(deq_v[6, ps - 1]),
                               np.asarray(k_new[1, 0] + 1.0), **INT8_TOLS)
    touched = np.nonzero(np.asarray(
        jnp.any(new["k_pages"] != 0, axis=(1, 2, 3))))[0].tolist()
    assert set(touched) <= {0, 2, 6}


def test_quantized_block_write_equals_sequential():
    """One S-token block scatter == S single-token writes, bit-exact,
    for values AND scales (per-slot scales make this possible — a
    per-page scale would requantize neighbors on every append)."""
    cfg = get_config("qwen3-0.6b").reduced()
    ps, P, S, B = 16, 8, 5, 2
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    cache = attn_lib.make_paged_kv_cache(cfg, P, ps, jnp.float32,
                                         kv_dtype="int8")
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([ps - 2, 3], jnp.int32)   # row 0 crosses a page
    key = jax.random.PRNGKey(7)
    kb = jax.random.normal(key, (B, S, Hkv, hd))
    vb = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    blk = attn_lib.paged_cache_write_block(cache, kb, vb, pos, bt)
    seq = cache
    for s in range(S):
        seq = attn_lib.paged_cache_write(seq, kb[:, s:s + 1], vb[:, s:s + 1],
                                         pos + s, bt)
    for leaf in ("k_pages", "v_pages", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(blk[leaf]),
                                      np.asarray(seq[leaf]))


def test_gather_paged_kv_dequantizes():
    cfg = get_config("qwen3-0.6b").reduced()
    ps, P = 16, 6
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    cache = attn_lib.make_paged_kv_cache(cfg, P, ps, jnp.float32,
                                         kv_dtype="int8")
    bt = jnp.asarray([[1, 2]], jnp.int32)
    kb = jax.random.normal(jax.random.PRNGKey(2), (1, 2 * ps, Hkv, hd))
    new = attn_lib.paged_cache_write_block(
        cache, kb, kb * 2.0, jnp.zeros((1,), jnp.int32), bt)
    k, v = attn_lib.gather_paged_kv(new, bt)
    assert k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(k), np.asarray(kb), **INT8_TOLS)
    np.testing.assert_allclose(np.asarray(v), np.asarray(kb * 2.0),
                               **INT8_TOLS)


# ---------------------------------------------------------------------------
# kv_storage_dtype validation
# ---------------------------------------------------------------------------

def test_kv_storage_dtype_resolution():
    assert kv_storage_dtype("auto", jnp.bfloat16) == (jnp.bfloat16, False)
    assert kv_storage_dtype("fp32", jnp.bfloat16) == (jnp.float32, False)
    assert kv_storage_dtype("bf16", jnp.float32) == (jnp.bfloat16, False)
    assert kv_storage_dtype("int8", jnp.float32) == (jnp.int8, True)
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_storage_dtype("int4", jnp.float32)
    if FP8_DTYPE is not None:
        assert kv_storage_dtype("fp8", jnp.float32) == (FP8_DTYPE, True)


# ---------------------------------------------------------------------------
# debug_validate: corruption raises instead of silent clipping
# ---------------------------------------------------------------------------

def test_debug_validate_catches_corruption():
    B, H, Hkv, hd, ps, n_pages = 2, 4, 2, 32, 16, 3
    P = B * n_pages + 1
    key = jax.random.PRNGKey(0)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    lengths = jnp.asarray([n_pages * ps, ps + 2], jnp.int32)
    # clean table validates and runs
    ops.paged_decode_attention(q, kp, vp, bt, lengths, debug_validate=True)

    # corrupt a LIVE logical page of row 0 -> must raise, naming the row
    bad = bt.at[0, 1].set(P + 13)
    with pytest.raises(ValueError, match=r"\(0, 1, "):
        ops.paged_decode_attention(q, kp, vp, bad, lengths,
                                   debug_validate=True)
    with pytest.raises(ValueError):
        validate_block_table(np.asarray(bt.at[1, 0].set(-2)),
                             np.asarray(lengths), P, ps)

    # corruption BEYOND the live length is dead space — allowed (idle
    # rows legitimately point everything at quarantine)
    dead = bt.at[1, 2].set(P + 13)          # row 1 live only to ps+2
    out = ops.paged_decode_attention(q, kp, vp, dead, lengths,
                                     debug_validate=True)
    exp = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    # row 1's output unaffected by the dead-page id (clip semantics)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(exp[1]),
                               rtol=1e-5, atol=1e-5)


def test_silent_clip_would_have_corrupted():
    """The failure mode debug_validate exists for: without validation, an
    out-of-range live page id silently clips to the last pool page and
    returns a plausible—but wrong—result."""
    B, H, Hkv, hd, ps, n_pages = 1, 4, 2, 32, 16, 2
    P = 4
    key = jax.random.PRNGKey(5)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages)
    lengths = jnp.asarray([n_pages * ps], jnp.int32)
    bad = bt.at[0, 1].set(P + 7)
    good = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    wrong = ops.paged_decode_attention(q, kp, vp, bad, lengths)
    assert np.isfinite(np.asarray(wrong)).all()
    assert not np.allclose(np.asarray(wrong), np.asarray(good), atol=1e-3)
