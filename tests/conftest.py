import os

# Tests run on the single real CPU device (the dry-run alone forces 512
# placeholder devices). Cap compilation parallelism noise.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# Shared serving-test setup: one reduced real config ("small") and one
# hand-rolled 2-layer dense LM ("tiny"), plus engine/submit helpers —
# previously duplicated across test_engine*.py / test_prefill_bucketed.py.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def small_model():
    """qwen3-0.6b reduced to CPU size — the 'real config' engine tests."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("qwen3-0.6b").reduced().with_overrides(dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="session")
def tiny_model():
    """2-layer dense 64-dim LM — the fast engine-mechanics tests."""
    from repro.config import ModelConfig
    from repro.models import build_model
    cfg = ModelConfig(
        name="tiny-lm", family="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
        head_dim=16, tie_embeddings=True, dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_engine(model, params, **kw):
    """ServeEngine with the shared test defaults; kwargs override."""
    from repro.config import CAMDConfig, SamplingConfig
    from repro.serving import ServeEngine
    max_new = kw.pop("max_new", 8)
    defaults = dict(
        slots=6, cache_len=64,
        sampling=SamplingConfig(max_new_tokens=max_new, temperature=0.8),
        camd=CAMDConfig(samples_per_round=2, max_rounds=2, min_samples=2,
                        max_clusters=8),
        max_new_tokens=max_new, eos_id=1, seed=0)
    defaults.update(kw)
    return ServeEngine(model, params, **defaults)


def _submit(engine, cfg, n, seed=0, plen=6, uid0=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        engine.submit(_request(
            uid0 + i, rng.integers(2, cfg.vocab_size, plen).astype(np.int32)))


def _request(uid, prompt, evidence=None):
    from repro.serving import Request
    return Request(uid=uid, prompt=prompt, evidence=evidence)


@pytest.fixture(scope="session")
def mk_engine():
    return _mk_engine


@pytest.fixture(scope="session")
def submit_prompts():
    return _submit


# ---------------------------------------------------------------------------
# Paged decode kernel cases (test_paged_attention.py, test_kv_quant.py)
# ---------------------------------------------------------------------------

def block_edge_lengths(ps, n_pages):
    """One row per edge of the kernel's first block: one token, the
    block less one, the block, the block and one, the whole table."""
    from repro.kernels.paged_decode_attention import _pages_per_block
    T = _pages_per_block(ps, n_pages) * ps
    assert T < n_pages * ps, "table must be wider than one block"
    return jnp.asarray([1, T - 1, T, T + 1, n_pages * ps], jnp.int32)


def nan_dead_pages(pages, block_table, lengths, ps):
    """``pages`` with every page that no live table entry names set to
    NaN: the dead entries' pages, page 0 and the spare ones."""
    bt, ln = np.asarray(block_table), np.asarray(lengths)
    live = np.arange(bt.shape[1])[None, :] * ps < ln[:, None]
    dead = np.ones(pages.shape[0], bool)
    dead[bt[live]] = False
    assert dead[bt[~live]].all()
    return pages.at[jnp.asarray(dead)].set(jnp.nan)
