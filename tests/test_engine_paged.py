"""Paged serving path: equivalence with the contiguous engine, pool
accounting proportional to live tokens, and page lifecycle under slot
recycling (the acceptance bar for the paged KV subsystem).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import _mk_engine as _mk_base, _submit
from repro.config import PagedKVConfig, SamplingConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import Request, ServeEngine

PAGE = PagedKVConfig(page_size=16)


def _mk_engine(model, params, **kw):
    kw.setdefault("n_candidates", 4)
    return _mk_base(model, params, **kw)


@pytest.mark.parametrize("mode", ["camd", "best_of_n"])
def test_paged_byte_identical_to_contiguous(small_model, mode):
    """The paged XLA path gathers pages into the same contiguous view the
    dense ring holds, so under a fixed seed the two engines must emit
    byte-identical tokens and identical accounting."""
    cfg, model, params = small_model
    res = {}
    for impl in ("xla", "paged"):
        eng = _mk_engine(model, params, mode=mode, impl=impl, paged_kv=PAGE)
        _submit(eng, cfg, 4)
        res[impl] = sorted(eng.run(), key=lambda r: r.uid)
        if impl == "paged":
            eng.pool.check()
            assert eng.pool.in_use == 0          # everything returned
    for a, b in zip(res["xla"], res["paged"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.tokens_spent == b.tokens_spent
        assert a.rounds == b.rounds
        assert a.n_candidates == b.n_candidates


def test_resident_kv_proportional_to_live_tokens(small_model):
    """Pool accounting: a single greedy request must peak at exactly
    ceil((prompt+new)/page_size) pages — resident KV scales with live
    tokens, not with slots × cache_len."""
    cfg, model, params = small_model
    ps = PAGE.page_size
    eng = _mk_engine(model, params, mode="greedy", impl="paged",
                     paged_kv=PAGE, slots=6)
    plen, max_new = 6, 8
    _submit(eng, cfg, 1, plen=plen)
    (r,) = eng.run()
    expect_pages = -((plen + max_new) // -ps)    # ceil
    assert eng.pool.max_in_use == expect_pages
    stats = eng.kv_stats()
    assert stats["peak_kv_bytes"] == expect_pages * stats["bytes_per_page"]
    assert stats["peak_kv_bytes"] < stats["dense_equiv_bytes"]
    assert stats["resident_kv_bytes"] == 0       # drained after run


def test_candidates_share_prompt_pages(small_model):
    """R candidates of one request must hold the full prompt pages once
    (refcounted), copying only the partial tail page — the CoW saving."""
    cfg, model, params = small_model
    ps = PAGE.page_size
    plen = 2 * ps + 3                            # 2 full pages + tail of 3
    eng = _mk_engine(model, params, mode="best_of_n", n_candidates=4,
                     impl="paged", paged_kv=PAGE, cache_len=64,
                     max_new_tokens=4,
                     sampling=SamplingConfig(max_new_tokens=4,
                                             temperature=0.8))
    _submit(eng, cfg, 1, plen=plen)
    eng._schedule()                              # admit without stepping
    info = next(iter(eng._reqs.values()))
    assert len(info["prompt_pages"]) == 2
    n_live = sum(1 for s in range(eng.B) if eng._slot_req[s] >= 0)
    assert n_live == 4
    for p in info["prompt_pages"]:
        assert eng.pool.refcount(p) == 1 + n_live   # request hold + cands
    # 2 shared + one private tail each, nothing else
    assert eng.pool.in_use == 2 + n_live
    eng.run()
    eng.pool.check()
    assert eng.pool.in_use == 0


def test_paged_pool_funds_queued_requests(small_model):
    """A pool far smaller than slots × cache_len still serves a queue of
    requests: freed pages from finished candidates fund the next ones."""
    cfg, model, params = small_model
    ps = PAGE.page_size
    # 6 slots x 4 pages/slot dense-equivalent would be 24 pages + 1; give 13
    eng = _mk_engine(model, params, mode="camd", impl="paged",
                     paged_kv=PagedKVConfig(page_size=ps, num_pages=13))
    _submit(eng, cfg, 6)
    res = eng.run()
    assert len(res) == 6
    eng.pool.check()
    assert eng.pool.in_use == 0
    assert eng.pool.max_in_use <= 12


def test_backpressure_under_prompt_page_holds(small_model):
    """A pending request's prompt-page hold must not crash admission of
    queued requests (regression: pool exhaustion between rounds) — the
    engine queues instead, and everything still completes."""
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params, slots=2, cache_len=128,
        sampling=SamplingConfig(max_new_tokens=8, temperature=0.8),
        mode="best_of_n", n_candidates=4, max_new_tokens=8, eos_id=1,
        impl="paged", paged_kv=PagedKVConfig(page_size=16), seed=0)
    _submit(eng, cfg, 3, plen=64)   # 4 prompt pages pinned per request
    res = eng.run()
    assert sorted(r.uid for r in res) == [0, 1, 2]
    assert all(r.n_candidates == 4 for r in res)
    eng.pool.check()
    assert eng.pool.in_use == 0
    assert eng._reserved == 0


def test_impossible_pool_raises_sizing_error(small_model):
    """A pool that can never fit one candidate fails fast with a sizing
    error instead of spinning or corrupting pages."""
    cfg, model, params = small_model
    eng = _mk_engine(model, params, mode="greedy", impl="paged",
                     paged_kv=PagedKVConfig(page_size=16, num_pages=2),
                     cache_len=64)
    _submit(eng, cfg, 1, plen=20)
    with pytest.raises(RuntimeError, match="cannot admit"):
        eng.run()


def test_decode_page_counters_match_slot_positions(small_model):
    """``kv_stats()``'s decode-walk counters equal a count made at each
    launch from the device's own slot state: the active slots' live
    pages (length = position + 1, over the page size) and B x the table
    width."""
    cfg, model, params = small_model
    ps = 4
    eng = _mk_engine(model, params, mode="camd", impl="paged",
                     paged_kv=PagedKVConfig(page_size=ps), macro_steps=2)
    seen = {"live": 0, "table": 0}
    launch = eng._macro_fn

    def counted(params_, state, *args):
        act = np.asarray(state.active)
        pos = np.asarray(state.cache["pos"])[act]
        seen["live"] += int(sum(-(-(p + 1) // ps) for p in pos))
        seen["table"] += eng.B * state.cache["block_table"].shape[1]
        return launch(params_, state, *args)

    eng._macro_fn = counted
    _submit(eng, cfg, 3)
    eng.run()
    stats = eng.kv_stats()
    assert eng.macro_launches > 2
    assert seen["live"] > eng.macro_launches     # slots span several pages
    assert stats["decode_pages_live"] == seen["live"]
    assert stats["decode_pages_table"] == seen["table"]
    assert stats["decode_pages_table"] == \
        eng.macro_launches * eng.B * (eng.cache_len // ps)
    eng.reset_stats()
    assert eng.kv_stats()["decode_pages_live"] == 0
    assert eng.kv_stats()["decode_pages_table"] == 0


def test_paged_pallas_kernel_path_runs(small_model):
    """impl="paged_pallas" (block-table kernel via ops dispatch) completes
    the same workload with plausible outputs."""
    cfg, model, params = small_model
    eng = _mk_engine(model, params, mode="camd", impl="paged_pallas",
                     paged_kv=PAGE)
    _submit(eng, cfg, 3)
    res = eng.run()
    assert len(res) == 3
    for r in res:
        assert np.isfinite(r.best_score)
        assert len(r.tokens) >= 1
    eng.pool.check()
    assert eng.pool.in_use == 0


def test_paged_vlm_evidence(small_model):
    """Evidence tokens extend the prompt span; the paged path must account
    for them identically to the contiguous path."""
    cfg = get_config("internvl2-2b").reduced().with_overrides(dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    res = {}
    for impl in ("xla", "paged"):
        eng = _mk_engine(model, params, mode="camd", impl=impl,
                         paged_kv=PAGE, slots=4)
        rng = np.random.default_rng(0)
        for i in range(2):
            ev = rng.standard_normal((cfg.num_evidence_tokens,
                                      cfg.evidence_dim)).astype(np.float32)
            eng.submit(Request(uid=i, prompt=rng.integers(
                2, cfg.vocab_size, 6).astype(np.int32), evidence=ev))
        res[impl] = sorted(eng.run(), key=lambda r: r.uid)
        if impl == "paged":
            eng.pool.check()
            assert eng.pool.in_use == 0
    for a, b in zip(res["xla"], res["paged"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
