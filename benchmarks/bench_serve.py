"""Serving throughput — macro-step fused decode vs the per-token loop —
plus the coverage-aware traffic-scheduler scenario.

Measures the engine-level win of the device-resident decode loop
(``ServeEngine(macro_steps=K)``, a ``lax.while_loop`` over K
decode+sample+CAMD steps with pre-staged page frontiers) against the
legacy host loop (``macro_steps=0``): tokens/sec, wall-clock, and —
the quantity the refactor exists to shrink — host synchronizations per
generated token.

Grid: macro-step K ∈ {0 (per-token loop), 1, 8, 32} × impl ∈ {xla, paged}
× mode ∈ {camd, best_of_n}. Each cell warms up once (jit compile +
first-run allocation on a throwaway request batch), then times a fresh
request batch on the same engine so compiled functions are reused.
Every cell completes the same token work (fixed CAMD round budget, no
early eos, uniform bucketed prefill), so tokens/sec and us/token are
comparable across the grid; page size and the default K come from a
committed ``BENCH_autotune.json`` when present (``autotune.load_tuned``).

The **quantized scenario** serves the trained chain-oracle workload
greedily under kv_dtype ∈ {auto, fp32, int8, fp8†}: oracle accuracy per
storage mode, true resident-KV bytes (values + scales), and the
tolerance-0 stream identity (fp32 == auto byte-identical). †fp8 only
where the jax build has float8_e4m3fn.

The **speculative scenario** decodes a shared-prefix greedy workload
with the n-gram draft + block-verify loop on (``spec_k=4``) and off on
a deep-cache model, asserting byte-identical streams and recording the
decode-throughput speedup (gated at 1.5x by ``check_regression``) in a
``speculative`` section.

The **scheduler scenario** trains a small LM on the arithmetic-chain
oracle task, builds heavy-tailed traffic (Pareto-distributed chain
difficulty — many easy, few hard — over a shared page-aligned prompt
preamble) and serves the SAME workload under ``fifo`` and ``coverage``
policies at an equal global token budget, reporting oracle accuracy,
easy/hard token allocation, starvation, and prefix-cache reuse in a
``scheduler`` section of ``BENCH_serve.json``.

The **sharded scenario** (multi-device runtimes only — on CPU force
host devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
serves the same workload on a single device and on an N-way data
mesh (``ServeEngine(mesh=...)``: decode batch sharded on the data axis,
KV page pool on the page axis) and records throughput plus the hard
invariant — byte-identical token streams — in a ``sharded`` section.
Single-device runtimes record the section as skipped.

The **open-loop scenario** serves the heavy-tailed shared-prefix
workload through the async streaming front-end under Poisson and bursty
arrival processes (offered at 0.7x the measured closed-loop capacity),
recording SLO metrics — p50/p99 TTFT from scheduled arrival, p50/p99
per-output-token latency (both also bucketed by prompt length), goodput
at an adaptive TTFT SLO, and tokens/s at saturation — plus a
cancellation cell asserting the abort path returns every page, slot,
and byte of scheduler commitment, in an ``open_loop`` section.

The **multimodal scenario** serves a shared-image heavy-tailed workload
(most requests ask about the SAME hot image over a shared prompt
preamble — the retrieval/chat pattern image-prefix caching exists for)
through the vision-language engine with the image prefix cache off and
on, recording the image-prefix cache hit rate, vision-tower encode vs
feature-memo counts, prefill tokens actually computed, and TTFT with
and without image reuse — plus the deterministic gates: dense, paged,
and paged+cache streams byte-identical, the shared image must hit, and
the reuse cell must compute strictly fewer prefill tokens
(``multimodal`` section).

The **chunked-prefill scenario** saturates a small greedy engine with
short prompts and queues long prompts behind them, then serves the SAME
workload with chunked prefill off (``prefill_chunk=0``) and on (the
autotuned chunk size): with chunking on, the long prompts' page-aligned
chunks run while they are still *queued* — prefill overlaps the shorts'
decode instead of serializing after it — so the long-prompt TTFT bucket
must improve while streams stay byte-identical and decode tokens/s
stays within the regression tolerance (``chunked_prefill`` section).

Writes ``BENCH_serve.json``; ``--smoke`` runs a reduced grid for CI and
``--sections grid,open_loop`` limits the run to named sections.

  python -m benchmarks.bench_serve [--smoke] [--sections a,b,...]
"""
from __future__ import annotations

import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.config import (CAMDConfig, ModelConfig, PagedKVConfig,
                          SamplingConfig, TrainConfig)
from repro.data import ChainTask, lm_batches
from repro.data.synthetic import SEP
from repro.models import build_model
from repro.serving import Request, ServeEngine
from repro.training import train


def _bench_model():
    cfg = ModelConfig(
        name="bench-serve-lm", family="dense", num_layers=4, d_model=256,
        num_heads=4, num_kv_heads=2, d_ff=768, vocab_size=512,
        head_dim=64, tie_embeddings=True, dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _submit(eng, cfg, n, uid0=0, seed=0, plen=12):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(Request(uid=uid0 + i, prompt=rng.integers(
            2, cfg.vocab_size, plen).astype(np.int32)))


def _assert_clean(eng):
    """Every timed cell must start from zeroed telemetry. Cells reuse
    one warm engine (recompiling per cell would put jit time on the
    clock), and an earlier version hand-reset an ad-hoc subset of
    counters — sched_stats()/kv_stats() numbers silently carried over
    from the warmup into the recorded rows. ``ServeEngine.reset_stats``
    now owns the full counter list; this asserts nothing leaks through.
    """
    assert eng.total_tokens == 0 and eng.total_steps == 0
    assert eng.macro_launches == 0 and eng.host_syncs == 0
    assert eng.spec_drafted == 0 and eng.spec_accepted == 0
    s = eng.sched_stats()
    assert s["admitted_candidates"] == 0 and s["prefill_calls"] == 0
    assert s["cancelled_requests"] == 0
    if eng.paged:
        k = eng.kv_stats()
        assert k["frontier_staged"] == 0 and k["frontier_peak_stage"] == 0


def _run_cell(cfg, model, params, *, impl, mode, macro_steps, requests,
              max_new, reps=3, page_size=16):
    """One equal-work grid cell.

    Every cell completes the IDENTICAL number of tokens, so tokens/sec
    is comparable across the whole grid: bucketed prefill is on for all
    K (an earlier version disabled it at K=0, which changed admission
    batching, hence sampled streams, hence early stopping — the
    committed baseline once compared 256-token cells against 192-token
    ones); min_samples pins CAMD to its full round budget; eos is an
    out-of-vocab id so no candidate stops early."""
    eng = ServeEngine(
        model, params, slots=8, cache_len=128,
        sampling=SamplingConfig(max_new_tokens=max_new, temperature=0.8),
        camd=CAMDConfig(samples_per_round=4, max_rounds=2, min_samples=8),
        mode=mode, n_candidates=4, max_new_tokens=max_new,
        eos_id=cfg.vocab_size,
        impl=impl, paged_kv=PagedKVConfig(page_size=page_size),
        macro_steps=macro_steps,
        bucket_prefill=True,
        seed=0)
    # warmup: compile every jitted fn on a throwaway batch of the SAME
    # size as the timed one (prefill buckets / admission widths are
    # shape-specialized — a mismatch would put recompiles on the clock)
    _submit(eng, cfg, requests, uid0=10_000, seed=1)
    eng.run()
    # best-of-reps: shared CI containers jitter wall clock by integer
    # factors between consecutive identical runs, so a single timed batch
    # regularly mis-ranks cells (the committed baseline once recorded the
    # paged macro-step loop "slower" than the per-token loop this way).
    # The max rate over identical-prompt batches is the stable statistic.
    best_rate, min_wall = 0.0, float("inf")
    for rep in range(reps):
        eng.reset_stats()
        _assert_clean(eng)
        _submit(eng, cfg, requests, uid0=1000 * (rep + 1), seed=2)
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        best_rate = max(best_rate, eng.total_tokens / max(wall, 1e-9))
        min_wall = min(min_wall, wall)
    return {
        "impl": impl,
        "mode": mode,
        "macro_steps": macro_steps,
        "reps": reps,
        "wall_s": min_wall,
        "tokens": eng.total_tokens,
        "device_steps": eng.total_steps,
        "tokens_per_s": best_rate,
        "us_per_token": 1e6 / max(best_rate, 1e-9),
        "host_syncs": eng.host_syncs,
        "syncs_per_token": eng.host_syncs / max(eng.total_tokens, 1),
        "macro_launches": eng.macro_launches,
    }


# ---------------------------------------------------------------------------
# Speculative scenario: n-gram draft + block verify vs sequential greedy
# ---------------------------------------------------------------------------

def _spec_model():
    """Small deep-cache model for the speculative scenario: decode cost
    is attention/KV-dominated, the regime speculation amortizes (the
    block verify reads the KV cache once per ~spec_k tokens instead of
    once per token)."""
    cfg = ModelConfig(
        name="bench-spec-lm", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=384, vocab_size=256,
        head_dim=32, tie_embeddings=True, dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _run_spec_cell(model, params, *, impl, spec_k, requests, max_new,
                   cache_len, reps):
    """One speculative cell: greedy decode of a shared repetitive prompt.

    Greedy streams must be byte-identical spec on/off, so top-p /
    repetition-penalty are disabled — greedy emits the raw argmax and
    the nucleus sort would only burn time in both engines without
    touching the output."""
    eng = ServeEngine(
        model, params, slots=8, cache_len=cache_len,
        sampling=SamplingConfig(max_new_tokens=max_new, temperature=0.0,
                                top_p=1.0, repetition_penalty=1.0),
        mode="greedy", n_candidates=1, max_new_tokens=max_new, eos_id=1,
        impl=impl, paged_kv=PagedKVConfig(page_size=16),
        macro_steps=8, spec_k=spec_k, seed=0)

    def submit(uid0):
        # shared-prefix workload: every request decodes the same
        # repeated-token prompt (n-gram fuel from position 0)
        for i in range(requests):
            eng.submit(Request(uid=uid0 + i,
                               prompt=np.full(12, 7, np.int32)))

    submit(10_000)
    eng.run()                                  # warmup / compile
    best_rate, min_wall, streams = 0.0, float("inf"), None
    for rep in range(reps):
        eng.reset_stats()
        _assert_clean(eng)
        submit(1000 * (rep + 1))
        t0 = time.perf_counter()
        res = eng.run()
        wall = time.perf_counter() - t0
        best_rate = max(best_rate, eng.total_tokens / max(wall, 1e-9))
        min_wall = min(min_wall, wall)
        if rep == 0:
            streams = [[int(t) for t in r.tokens]
                       for r in sorted(res, key=lambda r: r.uid)]
    row = {
        "impl": impl,
        "spec_k": spec_k,
        "wall_s": min_wall,
        "tokens": eng.total_tokens,
        "device_steps": eng.total_steps,
        "tokens_per_s": best_rate,
        "drafted": eng.spec_drafted,
        "accepted": eng.spec_accepted,
        "acceptance": eng.spec_accepted / max(eng.spec_drafted, 1),
    }
    return row, streams


def run_speculative_scenario(smoke: bool = False) -> dict:
    """Greedy decode with the n-gram draft + block verify loop on vs
    off: streams must be byte-identical; decode throughput should gain
    >= 1.5x on the shared-prefix workload (gated by check_regression)."""
    cfg, model, params = _spec_model()
    del cfg
    requests = 6
    max_new, cache_len, reps = (240, 384, 2) if smoke else (360, 512, 3)
    rows, headline = [], {"equal_outputs": True}
    for impl in ["xla", "paged"]:
        base_row, base_streams = _run_spec_cell(
            model, params, impl=impl, spec_k=0, requests=requests,
            max_new=max_new, cache_len=cache_len, reps=reps)
        spec_row, spec_streams = _run_spec_cell(
            model, params, impl=impl, spec_k=4, requests=requests,
            max_new=max_new, cache_len=cache_len, reps=reps)
        same = base_streams == spec_streams
        headline["equal_outputs"] &= same
        speedup = spec_row["tokens_per_s"] / max(base_row["tokens_per_s"],
                                                 1e-9)
        headline[f"speedup_{impl}"] = speedup
        headline[f"acceptance_{impl}"] = spec_row["acceptance"]
        rows += [base_row, spec_row]
        print(f"spec   {impl:6s} k=4: {base_row['tokens_per_s']:8.1f} -> "
              f"{spec_row['tokens_per_s']:8.1f} tok/s ({speedup:.2f}x), "
              f"accept {spec_row['acceptance']:.0%}, "
              f"streams {'identical' if same else 'DIVERGED'}")
    return {"requests": requests, "max_new": max_new,
            "cache_len": cache_len, "rows": rows, "headline": headline}


# ---------------------------------------------------------------------------
# Sharded scenario: N-way mesh vs single device, identical streams
# ---------------------------------------------------------------------------

def _stream_digest(results):
    return [(r.uid, r.tokens.tolist(), r.tokens_spent, r.n_candidates)
            for r in sorted(results, key=lambda r: r.uid)]


def _run_sharded_cell(cfg, model, params, *, impl, mesh, requests, max_new,
                      macro_steps=8):
    eng = ServeEngine(
        model, params, slots=8, cache_len=128,
        sampling=SamplingConfig(max_new_tokens=max_new, temperature=0.8),
        camd=CAMDConfig(samples_per_round=4, max_rounds=2, min_samples=4),
        mode="camd", n_candidates=4, max_new_tokens=max_new, eos_id=1,
        impl=impl, paged_kv=PagedKVConfig(page_size=16),
        macro_steps=macro_steps, mesh=mesh, seed=0)
    _submit(eng, cfg, requests, uid0=10_000, seed=1)      # warmup/compile
    eng.run()
    eng.reset_stats()              # report measured traffic only
    _assert_clean(eng)
    _submit(eng, cfg, requests, uid0=0, seed=2)
    t0 = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t0
    row = {
        "impl": impl,
        "dp": eng.dp,
        "wall_s": wall,
        "tokens": eng.total_tokens,
        "tokens_per_s": eng.total_tokens / max(wall, 1e-9),
        "macro_launches": eng.macro_launches,
    }
    if eng.paged:
        row["admitted_per_shard"] = \
            eng.sched_stats().get("admitted_per_shard", {})
    return row, _stream_digest(res)


def run_sharded_scenario(smoke: bool = False) -> dict:
    """Single-device vs mesh-sharded serving on the same workload: the
    streams must be byte-identical; throughput is recorded per impl."""
    n_dev = jax.device_count()
    if n_dev < 2:
        return {"skipped": f"single {jax.default_backend()} device — set "
                           f"XLA_FLAGS=--xla_force_host_platform_device_"
                           f"count=8 to exercise the mesh path"}
    from repro.launch.mesh import make_serve_mesh
    dp = max(d for d in (2, 4, 8) if d <= n_dev)          # slots=8 divisible
    mesh = make_serve_mesh(dp)
    cfg, model, params = _bench_model()
    requests, max_new = (3, 16) if smoke else (6, 32)
    rows, identical = [], True
    for impl in ["xla", "paged"]:
        base_row, base_streams = _run_sharded_cell(
            cfg, model, params, impl=impl, mesh=None,
            requests=requests, max_new=max_new)
        mesh_row, mesh_streams = _run_sharded_cell(
            cfg, model, params, impl=impl, mesh=mesh,
            requests=requests, max_new=max_new)
        same = base_streams == mesh_streams
        identical &= same
        rows += [base_row, mesh_row]
        print(f"sharded {impl:6s} dp={dp}: "
              f"{base_row['tokens_per_s']:8.1f} -> "
              f"{mesh_row['tokens_per_s']:8.1f} tok/s, "
              f"streams {'identical' if same else 'DIVERGED'}")
    return {"devices": n_dev, "dp": dp, "backend": jax.default_backend(),
            "rows": rows, "streams_identical": identical}


# ---------------------------------------------------------------------------
# Scheduler scenario: heavy-tailed difficulty at an equal global budget
# ---------------------------------------------------------------------------

CHAIN_BASE = 16

_CHAIN_MODELS: dict = {}    # steps -> (cfg, model, params); the scheduler
                            # and quantized scenarios share one training run


def _train_chain_model(steps: int):
    if steps in _CHAIN_MODELS:
        return _CHAIN_MODELS[steps]
    cfg = ModelConfig(
        name="bench-sched-lm", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=384, vocab_size=64, head_dim=32,
        tie_embeddings=True, dtype="float32")
    model = build_model(cfg, jnp.float32)
    data = ({"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"])}
            for b in lm_batches(cfg.vocab_size, 16, 48, seed=0,
                                base=CHAIN_BASE, max_chain=3))
    params, _, _ = train(
        model, TrainConfig(total_steps=steps, warmup_steps=steps // 10,
                           learning_rate=3e-3, remat=False),
        data, steps=steps, log_every=steps)
    _CHAIN_MODELS[steps] = (cfg, model, params)
    return cfg, model, params


def _heavy_tail_requests(task: ChainTask, n: int, seed: int = 7):
    """Pareto difficulty mix (many chain_len 0, few 3) over a shared
    page-aligned-ish preamble of solved segments (in-distribution for
    the trained LM, and 14 tokens => one full page at page_size 8 for
    the prefix cache to reuse)."""
    rng = np.random.default_rng(seed)

    def seg(k):
        p, ans, _ = task.sample(rng, chain_len=k)
        return np.concatenate([p, [ans, SEP]])

    preamble = np.concatenate([seg(2), seg(2)]).astype(np.int32)
    reqs = []
    for _ in range(n):
        k = min(3, int(rng.pareto(1.0)))
        p, ans, _ = task.sample(rng, chain_len=k)
        reqs.append((np.concatenate([preamble, p]).astype(np.int32),
                     int(ans), int(k)))
    return reqs


def _serve_policy(model, params, reqs, *, policy, budget):
    eng = ServeEngine(
        model, params, slots=4, cache_len=64,
        sampling=SamplingConfig(temperature=1.0, top_p=0.95,
                                repetition_penalty=1.0, max_new_tokens=3),
        camd=CAMDConfig(samples_per_round=2, max_rounds=4, min_samples=2,
                        delta=0.05, score_scale=3.0, lambda_c=0.2,
                        guidance_strength=0.5),
        mode="camd", n_candidates=8, eos_id=1, max_new_tokens=3,
        impl="paged", paged_kv=PagedKVConfig(page_size=8),
        sched_policy=policy, global_budget=budget, prefix_cache=True,
        seed=0)
    for i, (p, _ans, _k) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p))
    res = {r.uid: r for r in eng.run()}
    acc = float(np.mean([
        len(res[i].tokens) > 0 and int(res[i].tokens[0]) == reqs[i][1]
        for i in range(len(reqs))]))
    easy_ids = [i for i in range(len(reqs)) if reqs[i][2] <= 1]
    hard_ids = [i for i in range(len(reqs)) if reqs[i][2] >= 2]
    served_easy = [res[i].tokens_spent for i in easy_ids
                   if res[i].tokens_spent > 0]
    row = {
        "policy": policy,
        "global_budget": budget,
        "accuracy": acc,
        "total_tokens": eng.total_tokens,
        "easy_tokens": int(sum(res[i].tokens_spent for i in easy_ids)),
        "hard_tokens": int(sum(res[i].tokens_spent for i in hard_ids)),
        "easy_tokens_per_served": float(np.mean(served_easy))
        if served_easy else 0.0,
        "served": int(sum(res[i].tokens_spent > 0 for i in res)),
        "sched": eng.sched_stats(),
        "prefix_cache": eng.kv_stats().get("prefix_cache"),
    }
    return row


def run_scheduler_scenario(smoke: bool = False) -> dict:
    """fifo vs coverage on heavy-tailed traffic at equal token budget."""
    steps = 240 if smoke else 300
    n_req = 12 if smoke else 16
    cfg, model, params = _train_chain_model(steps)
    reqs = _heavy_tail_requests(ChainTask(base=CHAIN_BASE), n_req)
    # unbudgeted fifo reference sets the equal budget for the comparison
    ref = _serve_policy(model, params, reqs, policy="fifo", budget=0)
    budget = max(2, int(0.72 * ref["total_tokens"]))
    rows = [ref]
    for policy in ("fifo", "coverage"):
        row = _serve_policy(model, params, reqs, policy=policy,
                            budget=budget)
        rows.append(row)
        print(f"sched {policy:9s} @ budget {budget}: "
              f"acc={row['accuracy']:.3f} "
              f"easy/served={row['easy_tokens_per_served']:.1f} "
              f"starved={row['sched']['starved']}")
    out = {
        "n_requests": n_req,
        "difficulty_mix": [k for _, _, k in reqs],
        "train_steps": steps,
        "equal_budget": budget,
        "rows": rows,
    }
    fifo_b = next(r for r in rows[1:] if r["policy"] == "fifo")
    cov_b = next(r for r in rows[1:] if r["policy"] == "coverage")
    out["headline"] = {
        "accuracy_fifo": fifo_b["accuracy"],
        "accuracy_coverage": cov_b["accuracy"],
        "easy_per_served_fifo": fifo_b["easy_tokens_per_served"],
        "easy_per_served_coverage": cov_b["easy_tokens_per_served"],
        "coverage_beats_fifo":
            cov_b["accuracy"] >= fifo_b["accuracy"] and
            cov_b["easy_tokens_per_served"] <
            fifo_b["easy_tokens_per_served"],
    }
    return out


# ---------------------------------------------------------------------------
# Quantized-KV scenario: int8/fp8 pools vs fp32 on a trained oracle task
# ---------------------------------------------------------------------------

def _serve_quantized(model, params, reqs, *, kv_dtype):
    """Greedy CAMD-engine serve of the chain-oracle workload against one
    KV storage mode; accuracy is exact-match on the oracle answer, so a
    quantization-induced quality loss is directly visible."""
    eng = ServeEngine(
        model, params, slots=4, cache_len=64,
        sampling=SamplingConfig(temperature=0.0, top_p=1.0,
                                repetition_penalty=1.0, max_new_tokens=3),
        mode="greedy", n_candidates=1, eos_id=1, max_new_tokens=3,
        impl="paged", paged_kv=PagedKVConfig(page_size=8,
                                             kv_dtype=kv_dtype),
        macro_steps=8, seed=0)
    for i, (p, _ans, _k) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p))
    res = {r.uid: r for r in eng.run()}
    acc = float(np.mean([
        len(res[i].tokens) > 0 and int(res[i].tokens[0]) == reqs[i][1]
        for i in range(len(reqs))]))
    s = eng.kv_stats()
    return {
        "kv_dtype": kv_dtype,
        "accuracy": acc,
        "bytes_per_page": s["bytes_per_page"],
        "peak_kv_bytes": s["peak_kv_bytes"],
        "dense_equiv_bytes": s["dense_equiv_bytes"],
    }, [[int(t) for t in res[i].tokens] for i in range(len(reqs))]


def run_quantized_scenario(smoke: bool = False) -> dict:
    """Quantized paged-KV storage modes on the trained chain-oracle
    workload (shared with the scheduler scenario): greedy accuracy per
    kv_dtype, true resident-KV bytes, and the tolerance-0 stream
    identity (fp32 == auto on an fp32 engine). check_regression gates
    int8 bytes <= 0.55x fp32 and the accuracy delta."""
    from repro.models.attention import FP8_DTYPE
    steps = 240 if smoke else 300
    n_req = 12 if smoke else 16
    cfg, model, params = _train_chain_model(steps)
    del cfg
    reqs = _heavy_tail_requests(ChainTask(base=CHAIN_BASE), n_req)
    dtypes = ["auto", "fp32", "int8"] + (["fp8"] if FP8_DTYPE else [])
    rows, streams = [], {}
    for kvd in dtypes:
        row, st = _serve_quantized(model, params, reqs, kv_dtype=kvd)
        rows.append(row)
        streams[kvd] = st
        print(f"quant  {kvd:5s}: acc={row['accuracy']:.3f} "
              f"bytes/page={row['bytes_per_page']} "
              f"peak={row['peak_kv_bytes']}")
    by = {r["kv_dtype"]: r for r in rows}
    headline = {
        "fp32_identical_to_auto": streams["fp32"] == streams["auto"],
        "accuracy_fp32": by["fp32"]["accuracy"],
        "accuracy_int8": by["int8"]["accuracy"],
        "accuracy_delta_int8": by["fp32"]["accuracy"]
        - by["int8"]["accuracy"],
        "bytes_ratio_int8": by["int8"]["bytes_per_page"]
        / by["fp32"]["bytes_per_page"],
        "resident_ratio_int8": by["int8"]["peak_kv_bytes"]
        / max(by["fp32"]["peak_kv_bytes"], 1),
    }
    if "fp8" in by:
        headline["accuracy_fp8"] = by["fp8"]["accuracy"]
        headline["bytes_ratio_fp8"] = by["fp8"]["bytes_per_page"] \
            / by["fp32"]["bytes_per_page"]
    return {"n_requests": n_req, "train_steps": steps, "rows": rows,
            "headline": headline}


# ---------------------------------------------------------------------------
# Open-loop scenario: SLO metrics under Poisson / bursty arrivals
# ---------------------------------------------------------------------------

def _open_loop_engine(model, params, *, max_new):
    """Greedy paged engine for the open-loop cells. Greedy streams are
    schedule-invariant (one deterministic candidate per request), so the
    open-loop runs — whatever admission order the arrival process
    produces — must reproduce the closed-loop reference streams
    byte-for-byte. eos is out-of-vocab so every request emits exactly
    ``max_new`` tokens (equal work across cells)."""
    return ServeEngine(
        model, params, slots=4, cache_len=64,
        sampling=SamplingConfig(temperature=0.0, top_p=1.0,
                                repetition_penalty=1.0,
                                max_new_tokens=max_new),
        mode="greedy", n_candidates=1, eos_id=model.cfg.vocab_size,
        max_new_tokens=max_new,
        impl="paged", paged_kv=PagedKVConfig(page_size=8),
        prefix_cache=True, macro_steps=4, seed=0)


def run_open_loop_scenario(smoke: bool = False) -> dict:
    """Open-loop arrivals over the heavy-tailed shared-prefix workload.

    A closed-loop reference run (all requests pre-staged) measures
    capacity and pins the golden streams; open-loop cells then offer the
    SAME requests as a Poisson and a bursty arrival process at 0.7x the
    measured capacity (queueing counts against TTFT), plus a saturation
    cell (every arrival at t=0) for tokens/s under full queueing and a
    cancellation cell (a third of the clients disconnect after their
    first streamed token) asserting the abort path leaks nothing."""
    from repro.serving.traffic import (ARRIVALS, poisson_arrivals,
                                       run_open_loop)
    steps = 240 if smoke else 300
    n_req = 10 if smoke else 16
    max_new = 8 if smoke else 16
    cfg, model, params = _train_chain_model(steps)
    del cfg
    prompts = [p for p, _ans, _k in
               _heavy_tail_requests(ChainTask(base=CHAIN_BASE), n_req)]
    eng = _open_loop_engine(model, params, max_new=max_new)

    def reqs(uid0):
        return [Request(uid=uid0 + i, prompt=p)
                for i, p in enumerate(prompts)]

    for r in reqs(10_000):                    # warmup / compile
        eng.submit(r)
    eng.run()
    eng.reset_stats()
    _assert_clean(eng)
    for r in reqs(0):                         # closed-loop reference
        eng.submit(r)
    t0 = time.perf_counter()
    ref = {r.uid: [int(t) for t in r.tokens] for r in eng.run()
           if r.uid < 10_000}
    closed_wall = time.perf_counter() - t0
    closed_rate = n_req / max(closed_wall, 1e-9)
    closed_tok_s = eng.total_tokens / max(closed_wall, 1e-9)
    # adaptive SLO: 4x the closed-loop per-request wall, floored at
    # 250ms — machine-relative, so the gate survives slow CI containers
    slo_ms = max(250.0, 4e3 * closed_wall / n_req)
    rate = 0.7 * closed_rate
    rows, match_all, completed_all = [], True, True
    for name in ("poisson", "bursty", "saturation"):
        uid0 = {"poisson": 1000, "bursty": 2000, "saturation": 3000}[name]
        arr = np.zeros(n_req) if name == "saturation" \
            else ARRIVALS[name](rate, n_req, seed=11)
        eng.reset_stats()
        _assert_clean(eng)
        traces, metrics = run_open_loop(eng, reqs(uid0), arr,
                                        slo_ttft_ms=slo_ms,
                                        length_buckets=(18,))
        same = all(ref[tr.uid - uid0] ==
                   [int(t) for t in eng.result(tr.uid).tokens]
                   for tr in traces)
        match_all &= same
        completed_all &= metrics["completed"] == n_req
        rows.append({"arrival": name, "rate_rps": rate,
                     "streams_match": same, **metrics})
        print(f"open   {name:10s}: ttft p99 {metrics['ttft_p99_ms']:7.1f}ms"
              f"  goodput {metrics['goodput_rps']:.2f} rps"
              f"  {metrics['tokens_per_s']:7.1f} tok/s"
              f"  streams {'identical' if same else 'DIVERGED'}")
    # cancellation cell: every third client disconnects after its first
    # streamed token; afterwards the engine must hold NOTHING beyond the
    # resident prefix cache — no leaked pages, slots, or commitment
    cancel_uids = tuple(4000 + i for i in range(0, n_req, 3))
    eng.reset_stats()
    _assert_clean(eng)
    traces, metrics = run_open_loop(
        eng, reqs(4000), poisson_arrivals(rate, n_req, seed=13),
        slo_ttft_ms=slo_ms, cancel_uids=cancel_uids, cancel_after_tokens=1,
        length_buckets=(18,))
    survivors_match = all(
        ref[tr.uid - 4000] == [int(t) for t in eng.result(tr.uid).tokens]
        for tr in traces if not tr.cancelled)
    resident = len(eng.pool.prefix._nodes) if eng.pool.prefix else 0
    eng.pool.check()
    no_leaks = (eng.scheduler.committed == 0
                and eng.pool.in_use == resident
                and all(eng._slot_req[s] == -1 for s in range(eng.B))
                and metrics["cancelled"] == len(cancel_uids))
    match_all &= survivors_match
    rows.append({"arrival": "poisson+cancel", "rate_rps": rate,
                 "streams_match": survivors_match, "no_leaks": no_leaks,
                 **metrics})
    print(f"open   cancel    : {metrics['cancelled']} aborted, "
          f"{'no leaks' if no_leaks else 'LEAKED STATE'}")
    pois = rows[0]
    return {
        "n_requests": n_req, "max_new": max_new, "train_steps": steps,
        "slo_ttft_ms": slo_ms, "offered_rate_rps": rate,
        "closed_loop": {"wall_s": closed_wall,
                        "requests_per_s": closed_rate,
                        "tokens_per_s": closed_tok_s},
        "rows": rows,
        "headline": {
            "streams_match_closed_loop": match_all,
            "completed_all": completed_all,
            "no_leaks_after_cancel": no_leaks,
            "ttft_p99_ms": pois["ttft_p99_ms"],
            "ttft_by_bucket": pois["ttft_by_bucket"],
            "tpot_p99_ms": pois["tpot_p99_ms"],
            "goodput_rps": pois["goodput_rps"],
            "tokens_per_s_saturation": rows[2]["tokens_per_s"],
            "tokens_per_s_closed": closed_tok_s,
        },
    }


# ---------------------------------------------------------------------------
# Multimodal scenario: shared-image heavy-tailed traffic, image-prefix reuse
# ---------------------------------------------------------------------------

def _vlm_model():
    """Reduced vision-language model (llava-family): a real vision tower
    feeding image-token embeddings through the engine's prefill path."""
    from repro.configs import get_config
    cfg = get_config("llava_1_5_7b").reduced().with_overrides(
        dtype="float32")
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _vlm_image(cfg, idx):
    v = cfg.vision
    rng = np.random.default_rng(1000 + idx)
    return rng.standard_normal(
        (v.image_h, v.image_w, v.channels)).astype(np.float32)


def _multimodal_requests(cfg, n, seed=9):
    """Heavy-tailed image popularity over a shared prompt preamble:
    Pareto-distributed image ids (most requests share image 0, a short
    tail brings fresh ones) with a common 24-token preamble and a short
    per-request question tail — exactly the shape where the content-hash
    image prefix cache converts repeat images into skipped prefill."""
    rng = np.random.default_rng(seed)
    preamble = rng.integers(2, cfg.vocab_size, 24).astype(np.int32)
    reqs = []
    for _ in range(n):
        img_id = min(int(rng.pareto(1.0)), 3)
        tail = rng.integers(2, cfg.vocab_size,
                            int(rng.integers(2, 6))).astype(np.int32)
        reqs.append((np.concatenate([preamble, tail]).astype(np.int32),
                     img_id))
    return reqs, len({im for _, im in reqs})


def _run_multimodal_cell(cfg, model, params, reqs, *, impl, prefix_cache,
                         max_new, uid0):
    """One multimodal cell: greedy open-loop serve (all arrivals at t=0)
    of the shared-image workload. Greedy + fifo means every cell must
    stream byte-identically whatever the cache/impl — the image arm of
    the paged differential discipline. Returns (row, streams) keyed by
    request index. Image-encode counters are read after submit (the
    vision tower runs at submit time): on a cold engine they pin the
    tower-encode vs feature-memo split deterministically."""
    from repro.serving.traffic import run_open_loop
    images = {im: _vlm_image(cfg, im) for _, im in reqs}
    eng = ServeEngine(
        model, params, slots=4, cache_len=128,
        sampling=SamplingConfig(temperature=0.0, top_p=1.0,
                                repetition_penalty=1.0,
                                max_new_tokens=max_new),
        mode="greedy", n_candidates=1, max_new_tokens=max_new,
        eos_id=cfg.vocab_size,
        impl=impl, paged_kv=PagedKVConfig(page_size=8),
        prefix_cache=prefix_cache, macro_steps=4, seed=0)

    def mk(base):
        return [Request(uid=base + i, prompt=p, image=images[im])
                for i, (p, im) in enumerate(reqs)]

    for r in mk(uid0 + 10_000):               # warmup / compile
        eng.submit(r)
    cold_encodes, cold_hits = eng.image_encodes, eng.image_feat_hits
    eng.run()
    eng.reset_stats()
    _assert_clean(eng)
    traces, metrics = run_open_loop(eng, mk(uid0), np.zeros(len(reqs)),
                                    slo_ttft_ms=1e9)
    streams = {tr.uid - uid0: [int(t) for t in eng.result(tr.uid).tokens]
               for tr in traces}
    pc = None
    if eng.paged:
        eng.pool.check()
        pc = eng.kv_stats().get("prefix_cache")
    row = {
        "impl": impl,
        "prefix_cache": bool(prefix_cache),
        "image_encodes_cold": cold_encodes,
        "image_feat_hits_cold": cold_hits,
        "prefill_tokens": eng.prefill_tokens,
        "image_prefix": pc,
        **metrics,
    }
    return row, streams


def run_multimodal_scenario(smoke: bool = False) -> dict:
    """Shared-image heavy-tailed traffic through the vision-language
    engine: dense vs paged vs paged+image-prefix-cache. All three cells
    must stream byte-identically (greedy); the cache cell must hit on
    the shared image and compute strictly fewer prefill tokens; TTFT
    with/without image reuse is recorded (wall-clock, not gated)."""
    cfg, model, params = _vlm_model()
    n_req, max_new = (8, 8) if smoke else (12, 16)
    reqs, n_imgs = _multimodal_requests(cfg, n_req)
    cells = [("xla", False), ("paged", False), ("paged", True)]
    rows, streams = [], {}
    for i, (impl, pc) in enumerate(cells):
        row, st = _run_multimodal_cell(
            cfg, model, params, reqs, impl=impl, prefix_cache=pc,
            max_new=max_new, uid0=100_000 * (i + 1))
        rows.append(row)
        streams[(impl, pc)] = st
        hits = (row["image_prefix"] or {}).get("hit_tokens", 0)
        print(f"mmodal {impl:6s} cache={'on ' if pc else 'off'}: "
              f"prefill {row['prefill_tokens']:4d} tok  "
              f"hit_tokens {hits:4d}  "
              f"ttft p50 {row['ttft_p50_ms']:6.1f}ms  "
              f"{row['tokens_per_s']:7.1f} tok/s")
    off = next(r for r in rows if r["impl"] == "paged"
               and not r["prefix_cache"])
    on = next(r for r in rows if r["prefix_cache"])
    pc = on["image_prefix"] or {}
    identical = (streams[("xla", False)] == streams[("paged", False)]
                 == streams[("paged", True)])
    headline = {
        "streams_identical": identical,
        "n_requests": n_req,
        "distinct_images": n_imgs,
        "image_encodes_cold": on["image_encodes_cold"],
        "image_feat_hits_cold": on["image_feat_hits_cold"],
        "image_prefix_hits": pc.get("hits", 0),
        "image_prefix_hit_tokens": pc.get("hit_tokens", 0),
        "image_prefix_hit_rate": pc.get("hits", 0)
        / max(pc.get("probes", 0), 1),
        "prefill_tokens_no_reuse": off["prefill_tokens"],
        "prefill_tokens_reuse": on["prefill_tokens"],
        "prefill_reuse_savings": 1.0 - on["prefill_tokens"]
        / max(off["prefill_tokens"], 1),
        "ttft_p50_no_reuse_ms": off["ttft_p50_ms"],
        "ttft_p50_reuse_ms": on["ttft_p50_ms"],
        "ttft_p99_no_reuse_ms": off["ttft_p99_ms"],
        "ttft_p99_reuse_ms": on["ttft_p99_ms"],
        "ttft_reuse_improvement": off["ttft_p50_ms"]
        / max(on["ttft_p50_ms"], 1e-9),
    }
    return {"n_requests": n_req, "max_new": max_new,
            "distinct_images": n_imgs,
            "image_tokens": cfg.num_evidence_tokens,
            "rows": rows, "headline": headline}


# ---------------------------------------------------------------------------
# Chunked-prefill scenario: long-prompt TTFT under short-prompt load
# ---------------------------------------------------------------------------

CHUNK_BUCKETS = (32, 96)      # prompt-length buckets: lt32 / 32to96 / ge96


def _mixed_length_prompts(n_long, n_short, *, vocab, long_len=1024,
                          short_len=8, seed=5):
    """Head-of-line workload for the chunked-prefill A/B: ``n_long``
    long prompts listed FIRST, then ``n_short`` short prompts behind
    them. All arrivals at t=0 and every request fits in a slot, so fifo
    admission pins the order and the only variable is whether the short
    prompts' admission (and everyone's first token) must wait for the
    long prompts' monolithic prefills — with chunking on, the longs are
    budget-paced chunk jobs and the shorts are admitted around them."""
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, long_len).astype(np.int32)
            for _ in range(n_long)] + \
           [rng.integers(2, vocab, short_len).astype(np.int32)
            for _ in range(n_short)]


def _run_chunked_cell(model, params, prompts, *, chunk, max_new,
                      slo_ms=1e9, uid0=0):
    """One chunked-prefill cell: greedy open-loop serve of ``prompts``
    (everything offered at t=0) with ``prefill_chunk=chunk``. Returns
    (row, streams) — streams keyed by request index so cells with
    different uid bases compare directly. Greedy + fifo means the token
    streams must be byte-identical for every chunk size including 0."""
    from repro.serving.traffic import run_open_loop
    cache_len = -(-(max(len(p) for p in prompts) + max_new) // 64) * 64
    eng = ServeEngine(
        model, params, slots=len(prompts), cache_len=cache_len,
        sampling=SamplingConfig(temperature=0.0, top_p=1.0,
                                repetition_penalty=1.0,
                                max_new_tokens=max_new),
        mode="greedy", n_candidates=1, max_new_tokens=max_new,
        eos_id=model.cfg.vocab_size,
        impl="paged", paged_kv=PagedKVConfig(page_size=8),
        macro_steps=4, prefill_chunk=chunk, seed=0)
    for i, p in enumerate(prompts):               # warmup / compile
        eng.submit(Request(uid=uid0 + 10_000 + i, prompt=p))
    eng.run()
    eng.reset_stats()
    _assert_clean(eng)
    reqs = [Request(uid=uid0 + i, prompt=p) for i, p in enumerate(prompts)]
    traces, metrics = run_open_loop(
        eng, reqs, np.zeros(len(reqs)), slo_ttft_ms=slo_ms,
        length_buckets=CHUNK_BUCKETS)
    streams = {tr.uid - uid0: [int(t) for t in eng.result(tr.uid).tokens]
               for tr in traces}
    eng.pool.check()
    s = eng.sched_stats()
    row = {
        "prefill_chunk": chunk,
        "chunk_calls": s.get("chunk_calls", 0),
        "chunk_tokens": s.get("chunk_tokens", 0),
        "prefill_calls": s["prefill_calls"],
        **metrics,
    }
    return row, streams


def run_chunked_prefill_scenario(smoke: bool = False, *,
                                 chunk: int = 256) -> dict:
    """Chunked prefill off vs on on the head-of-line workload.

    Streams must be byte-identical (greedy + fifo). With chunking on,
    the short-prompt (lt32) TTFT bucket must improve sharply — shorts
    stop queueing behind whole-prompt prefills — while the long-prompt
    (ge96) p50 improves (the first long finishes its own prefill before
    the others' rather than after) and its p99 plus decode tokens/s stay
    within the regression tolerance (the budget-paced tail long pays a
    bounded pacing cost on a serial backend)."""
    cfg, model, params = _spec_model()
    # the long prompts stay 1024 tokens even in smoke: the head-of-line
    # effect the gates measure scales with prefill cost, and 512-token
    # longs on the tiny model drown it in per-chunk dispatch overhead
    n_long, n_short, long_len, max_new = \
        (2, 4, 1024, 12) if smoke else (2, 4, 1024, 24)
    # at least two chunks per long prompt, whatever autotune picked
    chunk = min(chunk, long_len // 2)
    prompts = _mixed_length_prompts(n_long, n_short, vocab=cfg.vocab_size,
                                    long_len=long_len)
    rows, streams = [], {}
    for c in (0, chunk):
        row, st = _run_chunked_cell(model, params, prompts, chunk=c,
                                    max_new=max_new, uid0=c * 1000)
        rows.append(row)
        streams[c] = st
        b = row["ttft_by_bucket"]
        print(f"chunk  c={c:<3d}: long p50/p99 "
              f"{b['ge96']['p50_ms']:6.1f}/{b['ge96']['p99_ms']:6.1f}ms  "
              f"short p99 {b['lt32']['p99_ms']:6.1f}ms  "
              f"{row['tokens_per_s']:7.1f} tok/s  "
              f"{row['chunk_calls']} chunk calls")
    off = next(r for r in rows if r["prefill_chunk"] == 0)
    on = next(r for r in rows if r["prefill_chunk"] == chunk)

    def bucket(row, name, q):
        return row["ttft_by_bucket"][name][q]

    headline = {
        "prefill_chunk": chunk,
        "streams_identical": streams[0] == streams[chunk],
        "chunk_calls": on["chunk_calls"],
        "chunk_tokens": on["chunk_tokens"],
        "ttft_p99_short_off_ms": bucket(off, "lt32", "p99_ms"),
        "ttft_p99_short_on_ms": bucket(on, "lt32", "p99_ms"),
        "ttft_short_improvement": bucket(off, "lt32", "p99_ms")
        / max(bucket(on, "lt32", "p99_ms"), 1e-9),
        "ttft_p50_long_off_ms": bucket(off, "ge96", "p50_ms"),
        "ttft_p50_long_on_ms": bucket(on, "ge96", "p50_ms"),
        "ttft_p99_long_off_ms": bucket(off, "ge96", "p99_ms"),
        "ttft_p99_long_on_ms": bucket(on, "ge96", "p99_ms"),
        "ttft_long_p99_ratio": bucket(on, "ge96", "p99_ms")
        / max(bucket(off, "ge96", "p99_ms"), 1e-9),
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
        "decode_ratio": on["tokens_per_s"] / max(off["tokens_per_s"],
                                                 1e-9),
    }
    return {"n_long": n_long, "n_short": n_short, "long_len": long_len,
            "max_new": max_new, "length_buckets": list(CHUNK_BUCKETS),
            "rows": rows, "headline": headline}


ALL_SECTIONS = ("grid", "speculative", "scheduler", "quantized", "sharded",
                "open_loop", "chunked_prefill", "multimodal")


def run(smoke: bool = False, sections=None) -> dict:
    cfg, model, params = _bench_model()
    from benchmarks.autotune import load_tuned
    tuned = load_tuned()["serve"]
    sections = tuple(sections) if sections else ALL_SECTIONS
    unknown = set(sections) - set(ALL_SECTIONS)
    if unknown:
        raise SystemExit(f"unknown bench sections {sorted(unknown)}; "
                         f"choose from {ALL_SECTIONS}")
    if smoke:
        impls, modes, ks = ["xla", "paged"], ["camd"], [0, 8]
        requests, max_new = 3, 16
    else:
        impls, modes, ks = ["xla", "paged"], ["camd", "best_of_n"], \
            [0, 1, 8, 32]
        requests, max_new = 6, 32
    # a committed autotune artifact shifts the default operating point
    if tuned["macro_steps"] not in ks:
        ks = sorted(ks + [tuned["macro_steps"]])
    out = {"config": {"smoke": smoke, "requests": requests,
                      "max_new": max_new, "slots": 8,
                      "page_size": tuned["page_size"],
                      "tuned": tuned,
                      "backend": jax.default_backend(),
                      "jax_version": jax.__version__,
                      "sections": list(sections)}}
    rows = []
    if "grid" in sections:
        for impl in impls:
            for mode in modes:
                for k in ks:
                    row = _run_cell(cfg, model, params, impl=impl,
                                    mode=mode, macro_steps=k,
                                    requests=requests, max_new=max_new,
                                    page_size=tuned["page_size"])
                    rows.append(row)
                    print(f"{impl:6s} {mode:10s} K={k:<3d} "
                          f"{row['tokens_per_s']:9.1f} tok/s  "
                          f"{row['syncs_per_token']:.4f} syncs/tok  "
                          f"wall {row['wall_s']:.2f}s")
        # headline: fused-vs-legacy speedup per (impl, mode)
        speedups = {}
        for impl in impls:
            for mode in modes:
                base = next(r for r in rows if r["impl"] == impl
                            and r["mode"] == mode
                            and r["macro_steps"] == ks[0])
                best = max((r for r in rows if r["impl"] == impl
                            and r["mode"] == mode),
                           key=lambda r: r["tokens_per_s"])
                speedups[f"{impl}/{mode}"] = {
                    "best_k": best["macro_steps"],
                    "tokens_per_s_legacy": base["tokens_per_s"],
                    "tokens_per_s_best": best["tokens_per_s"],
                    "speedup": best["tokens_per_s"]
                    / max(base["tokens_per_s"], 1e-9),
                    "sync_reduction":
                        base["syncs_per_token"]
                        / max(best["syncs_per_token"], 1e-9),
                }
        out["rows"], out["speedups"] = rows, speedups
    if "speculative" in sections:
        out["speculative"] = run_speculative_scenario(smoke)
    if "scheduler" in sections:
        out["scheduler"] = run_scheduler_scenario(smoke)
    if "quantized" in sections:
        out["quantized"] = run_quantized_scenario(smoke)
    if "sharded" in sections:
        out["sharded"] = run_sharded_scenario(smoke)
    if "open_loop" in sections:
        out["open_loop"] = run_open_loop_scenario(smoke)
    if "chunked_prefill" in sections:
        out["chunked_prefill"] = run_chunked_prefill_scenario(
            smoke, chunk=tuned["prefill_chunk"] or 256)
    if "multimodal" in sections:
        out["multimodal"] = run_multimodal_scenario(smoke)
    with open("BENCH_serve.json", "w") as f:
        json.dump(out, f, indent=2)
    print("wrote BENCH_serve.json")
    # cross-cell comparability: every grid cell must have completed the
    # same token work, or tokens/sec columns are not comparable
    for mode in (modes if "grid" in sections else []):
        per_mode = {r["tokens"] for r in rows if r["mode"] == mode}
        assert len(per_mode) == 1, \
            f"unequal completed-token work across {mode} cells: {per_mode}"
    if smoke:
        _smoke_asserts(out)
    return out


def _smoke_asserts(out: dict) -> None:
    """CI sanity on whichever sections ran."""
    if "rows" in out:
        rows = out["rows"]
        # the fused path must actually amortize host syncs
        fused = [r for r in rows if r["macro_steps"] >= 8]
        legacy = [r for r in rows if r["macro_steps"] == 0]
        assert all(r["tokens"] > 0 for r in rows)
        assert min(f["syncs_per_token"] for f in fused) < \
            min(l["syncs_per_token"] for l in legacy), \
            "macro-step loop did not reduce host syncs per token"
    if "speculative" in out:
        # speculation must not change greedy output, and must actually
        # pay for its verify width on the shared-prefix workload
        sh = out["speculative"]["headline"]
        assert sh["equal_outputs"], "speculative greedy streams diverged"
        for impl in ("xla", "paged"):
            assert sh[f"speedup_{impl}"] >= 1.5, \
                f"speculative speedup below 1.5x on {impl}: " \
                f"{sh[f'speedup_{impl}']:.2f}"
    if "scheduler" in out:
        # at equal budget, coverage-aware traffic scheduling must
        # match-or-beat fifo on quality (one request of sampling slack —
        # the trained-LM comparison is stochastic and CI's jax is
        # unpinned) while spending strictly fewer tokens per served easy
        # request, with the prefix cache actually reusing KV
        scheduler = out["scheduler"]
        h = scheduler["headline"]
        slack = 1.0 / scheduler["n_requests"]
        assert h["accuracy_coverage"] + slack >= h["accuracy_fifo"], h
        assert h["easy_per_served_coverage"] < h["easy_per_served_fifo"], h
        cov = next(r for r in scheduler["rows"][1:]
                   if r["policy"] == "coverage")
        assert cov["prefix_cache"]["hits"] > 0
        assert cov["total_tokens"] <= scheduler["equal_budget"]
    if "quantized" in out:
        # quantized KV: fp32 mode is a byte-identical no-op, int8 halves
        # (better) resident bytes and keeps oracle accuracy
        qh = out["quantized"]["headline"]
        assert qh["fp32_identical_to_auto"], \
            "kv_dtype=fp32 changed the serve trace on an fp32 engine"
        assert qh["bytes_ratio_int8"] <= 0.55, qh
        q_slack = 1.0 / out["quantized"]["n_requests"]
        assert qh["accuracy_delta_int8"] <= q_slack, qh
    if "sharded" in out and "skipped" not in out["sharded"]:
        # when the runtime has a mesh to shard over, sharding must be a
        # pure placement decision: byte-identical streams
        assert out["sharded"]["streams_identical"], out["sharded"]
    if "open_loop" in out:
        # open-loop arrivals reorder admission but greedy streams are
        # schedule-invariant; cancellation must leak nothing
        oh = out["open_loop"]["headline"]
        assert oh["streams_match_closed_loop"], oh
        assert oh["completed_all"], oh
        assert oh["no_leaks_after_cancel"], oh
        # bucketed TTFT must cover every completed request
        for row in out["open_loop"]["rows"]:
            if "ttft_by_bucket" in row:
                assert sum(b["n"] for b in row["ttft_by_bucket"].values()) \
                    == row["completed"], row
    if "chunked_prefill" in out:
        # chunking must be a pure latency optimization: byte-identical
        # greedy streams, and the chunk machinery must actually run
        ch = out["chunked_prefill"]["headline"]
        assert ch["streams_identical"], \
            "chunked prefill changed greedy token streams"
        assert ch["chunk_calls"] > 0 and ch["chunk_tokens"] > 0, ch
    if "multimodal" in out:
        # image prefill must be a pure storage/caching decision: dense,
        # paged, and paged+cache greedy streams byte-identical; the
        # shared hot image must actually hit and skip prefill work
        mh = out["multimodal"]["headline"]
        assert mh["streams_identical"], \
            "multimodal streams diverged across dense/paged/cache cells"
        assert mh["image_encodes_cold"] == mh["distinct_images"], mh
        assert mh["image_feat_hits_cold"] == \
            mh["n_requests"] - mh["distinct_images"], mh
        assert mh["image_prefix_hit_tokens"] > 0, mh
        assert mh["prefill_tokens_reuse"] < mh["prefill_tokens_no_reuse"], mh


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sections", default=None,
                    help="comma list from %s (default: all)"
                    % ",".join(ALL_SECTIONS))
    a = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(smoke=a.smoke,
        sections=a.sections.split(",") if a.sections else None)
