"""The engine's host spans and host-loop counters.

A few pumps of a tiny paged CAMD engine through ``AsyncServeFrontend``:
each launch has one ``serve.launch`` and one ``serve.sync`` span, the
phases nest inside ``serve.pump``, the launch gap is counted once per
launch after the first while the engine never goes idle, requests queued
behind full slots record their wait, ``reset_stats`` zeroes it all, the
spans reach the profiler's host plane, and each jitted program carries
a stable name.
"""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest

from conftest import _mk_engine
from repro.config import PagedKVConfig
from repro.serving import AsyncServeFrontend, Request

N_REQ = 5          # 4 slots, 2 candidates a round: 3 requests queue
PUMP_CHILDREN = ("serve.schedule", "serve.stage", "serve.launch",
                 "serve.sync", "serve.fold", "serve.finish")


def _engine(tiny_model):
    cfg, model, params = tiny_model
    return _mk_engine(model, params, mode="camd", macro_steps=2, slots=4,
                      max_new=8, eos_id=cfg.vocab_size, impl="paged",
                      paged_kv=PagedKVConfig(page_size=8))


def _serve(eng, n=N_REQ, uid0=0):
    """Submit ``n`` requests at once, then serve them to completion."""
    rng = np.random.default_rng(uid0)

    async def main():
        async with AsyncServeFrontend(eng, stream_tokens=False) as fe:
            for i in range(uid0, uid0 + n):
                await fe.submit(Request(
                    uid=i, prompt=rng.integers(2, 64, 6).astype(np.int32)))
            await fe.join()
    asyncio.run(main())


@pytest.fixture(scope="module")
def served(tiny_model):
    eng = _engine(tiny_model)
    _serve(eng)
    return eng, eng.span_stats()


def test_one_launch_and_one_sync_span_per_launch(served):
    eng, st = served
    assert eng.macro_launches > 2
    for name in ("serve.launch", "serve.sync", "serve.stage", "serve.fold"):
        assert st["spans"][name]["count"] == eng.macro_launches, name


def test_phases_nest_inside_the_pump(served):
    _eng, st = served
    spans = st["spans"]
    pump = spans["serve.pump"]
    assert sum(spans[n]["total_ns"] for n in PUMP_CHILDREN) \
        <= pump["total_ns"]
    assert all(s["max_ns"] <= s["total_ns"] for s in spans.values())
    last = st["last_pump"]
    assert set(last["children"]) <= set(PUMP_CHILDREN)
    assert 0 < sum(last["children"].values()) <= last["ns"] \
        <= pump["max_ns"]
    # admissions and prefill run inside the scheduling pass
    assert spans["serve.admit"]["total_ns"] \
        <= spans["serve.schedule"]["total_ns"]
    assert spans["serve.prefill"]["count"] >= 1
    assert spans["serve.dispatch"]["count"] >= pump["count"]


def test_launch_gap_counted_once_per_launch_after_the_first(served):
    eng, st = served
    # every request was submitted before the first pump: never idle
    assert st["launch_gaps"] == eng.macro_launches - 1
    assert st["launch_gap_ns"] > 0
    assert eng.sched_stats()["launch_gaps"] == st["launch_gaps"]


def test_queue_wait_of_requests_behind_full_slots(served):
    eng, st = served
    assert st["first_admissions"] == N_REQ
    assert st["queue_wait_ns"] > 0
    s = eng.sched_stats()
    assert s["queue_wait_ns"] == st["queue_wait_ns"]
    assert s["first_admissions"] == N_REQ


def test_idle_time_is_not_a_launch_gap(tiny_model):
    eng = _engine(tiny_model)
    _serve(eng, n=2)
    gaps, launches = eng.launch_gaps, eng.macro_launches
    _serve(eng, n=2, uid0=10)       # the engine went idle in between
    assert eng.launch_gaps == gaps + (eng.macro_launches - launches) - 1


def test_reset_stats_zeroes_counters_and_spans(tiny_model):
    eng = _engine(tiny_model)
    _serve(eng, n=2)
    assert eng.span_stats()["spans"]
    eng.reset_stats()
    st = eng.span_stats()
    assert st == {"launch_gap_ns": 0, "launch_gaps": 0, "queue_wait_ns": 0,
                  "first_admissions": 0, "spans": {}, "last_pump": None}
    s = eng.sched_stats()
    for k in ("launch_gap_ns", "launch_gaps", "queue_wait_ns",
              "first_admissions"):
        assert s[k] == 0, k
    # the first launch after a reset has no gap to count
    _serve(eng, n=2, uid0=10)
    assert eng.launch_gaps == eng.macro_launches - 1


def test_spans_on_the_profiler_host_plane(tiny_model, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(tiny_model)
    _serve(eng, n=1)                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, n=3, uid0=10)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    pd = ProfileData.from_file(path[0])
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name.startswith("serve.")]
    names = {n for n, *_ in events}
    assert {"serve.pump", "serve.launch", "serve.sync", "serve.admit",
            "serve.dispatch"} <= names
    pumps = [(a, b) for n, a, b, _ in events if n == "serve.pump"]
    for n, a, b, _ in events:
        if n in PUMP_CHILDREN:
            assert any(pa <= a and b <= pb for pa, pb in pumps), n
    admits = [s for n, _, _, s in events if n == "serve.admit"]
    assert {s["uid"] for s in admits} == {10, 11, 12}


def test_jitted_programs_have_stable_names(tiny_model):
    eng = _engine(tiny_model)
    assert {eng._macro_fn.__name__, eng._step_fn.__name__,
            eng._prefill_fn.__name__, eng._bucket_fn.__name__,
            eng._first_fn.__name__, eng._round_fn.__name__} == {
        "decode_launch", "decode_step", "prefill_row", "prefill_bucket",
        "first_tokens", "round_update"}


def test_lowered_modules_carry_the_program_names(tiny_model):
    import jax.numpy as jnp
    eng = _engine(tiny_model)
    toks = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.full((2,), 8, jnp.int32)
    cache = eng.model.make_cache(2, eng.cache_len, eng._dtype)
    text = eng._bucket_fn.lower(eng.params, toks, lens, cache).as_text()
    assert "jit_prefill_bucket" in text
    text = eng._macro_fn.lower(
        eng.params, eng.state, eng._decode_key, jnp.int32(0),
        jnp.zeros((eng.B, 1, eng.d), jnp.float32),
        jnp.zeros((eng.B, 1), jnp.int32)).as_text()
    assert "jit_decode_launch" in text
