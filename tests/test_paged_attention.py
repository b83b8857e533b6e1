"""Paged decode-attention correctness: Pallas (interpret) vs jnp oracle,
paged oracle vs contiguous oracle, and the paged cache-write layout.

Sweeps page sizes {16, 64, 128}, ragged live lengths, and GQA group
sizes — the block-padding and masking paths the serving engine leans on
— then tables several kernel blocks wide, lengths at the block edges,
and dead table entries naming NaN pages, which the kernel must never
read.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import block_edge_lengths, nan_dead_pages
from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models import attention as attn_lib

TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _setup(key, B, H, Hkv, hd, P, ps, n_pages, dtype, seed=0):
    q = jax.random.normal(key, (B, 1, H, hd)).astype(dtype)
    kp = jax.random.normal(jax.random.fold_in(key, 1),
                           (P, ps, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 2),
                           (P, ps, Hkv, hd)).astype(dtype)
    rng = np.random.default_rng(seed)
    # rows reference disjoint random pages, like a fragmented live pool
    perm = rng.permutation(P - 1) + 1          # page 0 = quarantine
    assert B * n_pages <= P - 1
    bt = jnp.asarray(perm[:B * n_pages].reshape(B, n_pages), jnp.int32)
    return q, kp, vp, bt


@pytest.mark.parametrize("ps", [16, 64, 128])
@pytest.mark.parametrize("Hkv,H", [(1, 4), (2, 8), (4, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_oracle(ps, Hkv, H, dtype):
    B, hd, n_pages = 3, 64, 4
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps + H)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages, dtype)
    # ragged: one-token row, mid-page row, exactly-full row
    lengths = jnp.asarray([1, (n_pages - 1) * ps + ps // 2 + 1, n_pages * ps],
                          jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **TOLS[dtype])


# tables wider than one kernel block; the block does not divide 21 or 5
MULTI_BLOCK = [(16, 80), (16, 21), (64, 5)]


@pytest.mark.parametrize("ps,n_pages", MULTI_BLOCK)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_multi_block(ps, n_pages, dtype):
    B, H, Hkv, hd = 5, 8, 2, 64
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps + n_pages)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages, dtype)
    lengths = block_edge_lengths(ps, n_pages)
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("ps,n_pages", MULTI_BLOCK)
def test_paged_kernel_never_reads_dead_pages(ps, n_pages):
    """Dead table entries name NaN pages: one NaN read anywhere would
    reach the output (0 * NaN is NaN), so a finite output equal to the
    oracle's on the clean pool proves no dead page was read."""
    B, H, Hkv, hd = 5, 8, 2, 64
    P = B * n_pages + 2
    key = jax.random.PRNGKey(ps * n_pages)
    q, kp, vp, bt = _setup(key, B, H, Hkv, hd, P, ps, n_pages, jnp.float32)
    lengths = block_edge_lengths(ps, n_pages)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    out = paged_decode_attention(q, nan_dead_pages(kp, bt, lengths, ps),
                                 nan_dead_pages(vp, bt, lengths, ps), bt,
                                 lengths, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               **TOLS[jnp.float32])


def test_paged_oracle_matches_contiguous_oracle():
    """Gathering the block table must reproduce dense decode attention
    exactly (fp32, <=1e-4): pages laid out contiguously == dense cache."""
    B, H, Hkv, hd, ps, n_pages = 2, 8, 2, 64, 16, 6
    S = ps * n_pages
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    q = jax.random.normal(key, (B, 1, H, hd))
    lengths = jnp.asarray([S // 3, S], jnp.int32)
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    dense = ref.decode_attention_ref(q, k, v, mask)

    # identity layout: row b's page i is pool page b*n_pages + i
    kp = k.reshape(B * n_pages, ps, Hkv, hd)
    vp = v.reshape(B * n_pages, ps, Hkv, hd)
    bt = jnp.arange(B * n_pages, dtype=jnp.int32).reshape(B, n_pages)
    paged = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    kern = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ps", [16, 64])
def test_paged_cache_write_layout(ps):
    """paged_cache_write must land token at pos p in page bt[b, p//ps],
    offset p%ps — and idle rows (table row = quarantine) must never
    corrupt live pages."""
    cfg = get_config("qwen3-0.6b").reduced()
    P, n_pages = 8, 4
    cache = attn_lib.make_paged_kv_cache(cfg, P, ps, jnp.float32)
    B = 3
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0], [0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([ps + 3, 2 * ps - 1, 10 ** 6], jnp.int32)  # row 2 idle
    k_new = jax.random.normal(jax.random.PRNGKey(0), (B, 1, Hkv, hd))
    new = attn_lib.paged_cache_write(cache, k_new, k_new + 1.0, pos, bt)
    np.testing.assert_array_equal(
        np.asarray(new["k_pages"][2, 3]), np.asarray(k_new[0, 0]))
    np.testing.assert_array_equal(
        np.asarray(new["k_pages"][6, ps - 1]), np.asarray(k_new[1, 0]))
    np.testing.assert_array_equal(
        np.asarray(new["v_pages"][6, ps - 1]), np.asarray(k_new[1, 0] + 1.0))
    # idle row clamps to its table (all-quarantine) — only page 0 dirtied
    touched = np.nonzero(np.asarray(
        jnp.any(new["k_pages"] != 0.0, axis=(1, 2, 3))))[0].tolist()
    assert set(touched) <= {0, 2, 6}
