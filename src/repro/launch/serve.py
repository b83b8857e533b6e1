"""Serving launcher: load (or train) a model and serve requests with CAMD.

    python -m repro.launch.serve --arch qwen3-0.6b --reduced --mode camd \
        --requests 8 --impl paged --page-size 16

``--reduced`` (the default) serves the config's CPU-sized variant in
float32; ``--no-reduced`` serves its published widths in the config's
dtype (bf16). ``build_model``/``build_engine``/``make_requests`` are the
one construction path, shared with ``chip_smoke.py``.

``--open-loop`` serves the same requests through the async streaming
front-end as a timed arrival process (``--arrival poisson|bursty`` at
``--arrival-rate`` rps) and prints SLO metrics — TTFT/TPOT percentiles
and goodput at the ``--slo-ms`` TTFT SLO — instead of batch results.
"""
import argparse

import numpy as np

import jax
import jax.numpy as jnp

from repro.config import (CAMDConfig, PagedKVConfig, SamplingConfig,
                          VisionConfig)
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model as _build_model
from repro.serving import Request, ServeEngine
from repro.training import load_checkpoint


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--config", default="qwen3-0.6b",
                    help="arch id ('llava-1.5-7b') or config module name "
                         "('llava_1_5_7b') — both spellings resolve")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the config's CPU-sized variant in float32 "
                         "(default); --no-reduced serves its published "
                         "widths in the config's dtype")
    ap.add_argument("--image-tokens", type=int, default=0,
                    help="multimodal serving: encode synthetic images "
                         "through the config's vision tower into N "
                         "image tokens per request (overrides the "
                         "config's evidence-token count; vision configs "
                         "only)")
    ap.add_argument("--image-pool", type=int, default=2,
                    help="distinct images the synthetic requests draw "
                         "from: repeats hit the submit-time feature "
                         "memo and, with --prefix-cache, the image-page "
                         "prefix cache")
    ap.add_argument("--xmodal-rescore", action="store_true",
                    help="rescore finished candidates' S_align through "
                         "the fused xmodal_score kernel (Eq. 8-9) "
                         "instead of the incremental aggregate")
    ap.add_argument("--mode", default="camd",
                    choices=["camd", "best_of_n", "self_consistency",
                             "greedy"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="synthetic prompt length in tokens (long prompts "
                         "exercise chunked prefill)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--impl", default="xla",
                    choices=["xla", "pallas", "paged", "paged_pallas"])
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool size; 0 = dense-equivalent worst case")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "fp32", "bf16", "int8", "fp8"],
                    help="paged KV pool storage dtype: auto = engine "
                         "param dtype; int8/fp8 store quantized pages "
                         "with per-(page, slot, kv-head) scales, "
                         "dequantized inside the attention kernels "
                         "(fp8 needs a jax build with float8_e4m3fn)")
    ap.add_argument("--macro-steps", type=int, default=8,
                    help="device decode steps per lax.while_loop launch; "
                         "0 = legacy per-token host loop")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative block length: draft up to K-1 "
                         "tokens per slot from the n-gram table and "
                         "verify them in one target forward (0/1 = off; "
                         "requires --macro-steps >= 1 and an "
                         "all-attention decoder)")
    ap.add_argument("--spec-mode", default="coverage",
                    choices=["coverage", "fixed"],
                    help="coverage: per-slot draft length shrinks toward "
                         "1 as the request's posterior coverage deficit "
                         "closes; fixed: always draft spec-k - 1 tokens")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=["fifo", "coverage"],
                    help="traffic policy: fifo (arrival order) or coverage "
                         "(rank pending work by posterior coverage deficit "
                         "+ expected marginal gain, with aging)")
    ap.add_argument("--global-budget", type=int, default=0,
                    help="hard token budget across the whole request "
                         "stream (0 = unlimited)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prompt-prefix KV reuse (paged "
                         "impls on all-attention decoders)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: split long prompts into "
                         "page-aligned chunks of this many tokens and "
                         "interleave them with decode launches (0 = "
                         "whole-prompt prefill; paged all-attention "
                         "decoders only, others degrade gracefully)")
    ap.add_argument("--prefill-chunk-budget", type=int, default=0,
                    help="max chunk tokens prefilled per engine turn "
                         "(0 = one chunk per turn)")
    ap.add_argument("--prefill-shards", type=int, default=0,
                    help="prefill/decode disaggregation: place prompt/"
                         "chunk pages on the first N data shards of the "
                         "page axis; decode shards read them cross-shard "
                         "(0 = prompt pages follow the admitting slot)")
    ap.add_argument("--kv-byte-budget", type=int, default=0,
                    help="resident-KV byte ceiling for the cross-request "
                         "prefix cache: cached-only pages are evicted "
                         "until resident KV bytes (incl. quant scales) "
                         "fall under it (0 = unbounded)")
    ap.add_argument("--serve-dp", type=int, default=0,
                    help="shard the decode batch + KV page pools across "
                         "N data-parallel devices (0 = single device; "
                         "on CPU combine with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--mesh", default="",
                    help="explicit serving mesh as 'dp,model' (e.g. "
                         "'4,2' = 4 data shards x 2-way tensor "
                         "parallel); overrides --serve-dp")
    ap.add_argument("--no-bucket-prefill", action="store_true",
                    help="disable length-bucketed batched prefill")
    ap.add_argument("--prefill-bucket-min", type=int, default=16,
                    help="smallest power-of-two prompt bucket")
    ap.add_argument("--open-loop", action="store_true",
                    help="serve through the async streaming front-end "
                         "with timed arrivals instead of a pre-staged "
                         "batch, and report SLO metrics (TTFT/TPOT "
                         "percentiles, goodput); needs --macro-steps >= 1")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty"],
                    help="open-loop arrival process")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="open-loop offered load, requests/s")
    ap.add_argument("--slo-ms", type=float, default=500.0,
                    help="TTFT SLO for the goodput metric, milliseconds")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds params, synthetic requests and sampling")
    return ap


def build_model(args):
    """(cfg, model, params) for ``--arch``/``--reduced``/``--image-tokens``:
    reduced runs in float32, full width in the config's dtype, params
    drawn from ``--seed`` (or loaded from ``--ckpt``)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().with_overrides(dtype="float32")
    if args.image_tokens:
        if cfg.vision is None:
            raise SystemExit(f"--image-tokens needs a vision config; "
                             f"{cfg.name} has no vision tower")
        v = cfg.vision
        cfg = cfg.with_overrides(
            num_evidence_tokens=args.image_tokens,
            vision=VisionConfig.for_tokens(
                args.image_tokens, patch=v.patch, num_layers=v.num_layers,
                d_model=v.d_model, num_heads=v.num_heads, d_ff=v.d_ff))
    model = _build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        params, _ = load_checkpoint(args.ckpt, params)
    return cfg, model, params


def cache_len_for(cfg, args) -> int:
    """Slot cache depth: prompt + image tokens + ``--max-new``, rounded
    up to the page size."""
    n = args.prompt_len + cfg.num_evidence_tokens + args.max_new
    return -(-n // args.page_size) * args.page_size


def build_engine(args, model, params) -> ServeEngine:
    """The serving engine the CLI flags describe (mesh included)."""
    mesh = None
    if args.mesh or args.serve_dp > 1:
        from repro.launch.mesh import make_serve_mesh
        if args.mesh:
            dp, mp = (list(map(int, args.mesh.split(","))) + [1])[:2]
        else:
            dp, mp = args.serve_dp, 1
        mesh = make_serve_mesh(dp, model=mp)
        print(f"serving mesh: {dict(mesh.shape)} over "
              f"{len(jax.devices())} {jax.default_backend()} devices")

    return ServeEngine(
        model, params, slots=args.slots,
        cache_len=cache_len_for(model.cfg, args),
        sampling=SamplingConfig(max_new_tokens=args.max_new),
        camd=CAMDConfig(),
        mode=args.mode, max_new_tokens=args.max_new, eos_id=1,
        impl=args.impl,
        paged_kv=PagedKVConfig(page_size=args.page_size,
                               num_pages=args.num_pages,
                               kv_dtype=args.kv_dtype,
                               kv_byte_budget=args.kv_byte_budget),
        macro_steps=args.macro_steps,
        bucket_prefill=not args.no_bucket_prefill,
        prefill_bucket_min=args.prefill_bucket_min,
        sched_policy=args.sched_policy,
        global_budget=args.global_budget,
        prefix_cache=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        prefill_chunk_budget=args.prefill_chunk_budget,
        prefill_shards=args.prefill_shards,
        mesh=mesh,
        spec_k=args.spec_k,
        spec_mode=args.spec_mode,
        xmodal_rescore=args.xmodal_rescore,
        seed=args.seed)


def make_requests(args, cfg):
    """``--requests`` synthetic requests drawn from ``--seed``: random
    prompts, plus an image from a pool of ``--image-pool`` distinct
    images (vision configs) or random evidence rows (evidence configs)."""
    rng = np.random.default_rng(args.seed)

    images = []
    if cfg.num_evidence_tokens and cfg.vision is not None:
        v = cfg.vision
        images = [rng.standard_normal(
            (v.image_h, v.image_w, v.channels)).astype(np.float32)
            for _ in range(max(1, args.image_pool))]

    def mk_request(i):
        prompt = rng.integers(2, cfg.vocab_size,
                              size=args.prompt_len).astype(np.int32)
        if images:
            # draw from a small shared pool: repeated images exercise
            # the submit-time feature memo and the image prefix cache
            return Request(uid=i, prompt=prompt,
                           image=images[int(rng.integers(len(images)))])
        ev = None
        if cfg.num_evidence_tokens:
            ev = rng.standard_normal(
                (cfg.num_evidence_tokens, cfg.evidence_dim)).astype(np.float32)
        return Request(uid=i, prompt=prompt, evidence=ev)

    return [mk_request(i) for i in range(args.requests)]


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, model, params = build_model(args)
    eng = build_engine(args, model, params)
    reqs = make_requests(args, cfg)

    if args.open_loop:
        from repro.serving.traffic import ARRIVALS, run_open_loop
        if args.macro_steps < 1:
            raise SystemExit("--open-loop drives the fused macro-step "
                             "loop; use --macro-steps >= 1")
        arrivals = ARRIVALS[args.arrival](
            args.arrival_rate, args.requests, seed=args.seed)
        traces, metrics = run_open_loop(eng, reqs, arrivals,
                                        slo_ttft_ms=args.slo_ms)
        for tr in traces:
            print(f"req {tr.uid}: arrival {tr.t_arrival * 1e3:7.1f}ms  "
                  f"ttft {(tr.t_first - tr.t_arrival) * 1e3:7.1f}ms  "
                  f"tokens={tr.n_tokens}")
        print(f"open loop [{args.arrival} @ {args.arrival_rate:.1f} rps]: "
              f"{metrics['completed']} completed over "
              f"{metrics['span_s']:.2f}s")
        print(f"  ttft p50/p99 {metrics['ttft_p50_ms']:.1f}/"
              f"{metrics['ttft_p99_ms']:.1f} ms   "
              f"tpot p50/p99 {metrics['tpot_p50_ms']:.1f}/"
              f"{metrics['tpot_p99_ms']:.1f} ms")
        print(f"  goodput {metrics['goodput_rps']:.2f} rps at "
              f"{args.slo_ms:.0f}ms TTFT SLO "
              f"({metrics['good_requests']}/{metrics['completed']}), "
              f"{metrics['tokens_per_s']:.1f} tok/s")
        results = []
    else:
        for r in reqs:
            eng.submit(r)
        results = eng.run()
    for r in results:
        print(f"req {r.uid}: candidates={r.n_candidates} rounds={r.rounds} "
              f"tokens={r.tokens_spent} p*={r.p_star:.3f} "
              f"early={r.stopped_early} out={r.tokens[:8].tolist()}")
    print(f"engine: {eng.total_steps} steps, {eng.total_tokens} tokens, "
          f"{eng.total_tokens / max(eng.total_steps * eng.B, 1):.2f} "
          f"slot-efficiency")
    print(f"macro-step: K={eng.macro_steps}, {eng.macro_launches} launches, "
          f"{eng.host_syncs} host syncs "
          f"({eng.host_syncs / max(eng.total_tokens, 1):.3f} per token)")
    if eng.spec:
        print(f"speculative: K={eng.spec_k} ({eng.spec_mode}), "
              f"{eng.spec_drafted} drafted, {eng.spec_accepted} accepted "
              f"({eng.spec_accepted / max(eng.spec_drafted, 1):.0%})")
    ss = eng.sched_stats()
    print(f"scheduler: {ss['policy']} admitted={ss['admitted_candidates']} "
          f"spent={ss['spent']}/{ss['global_budget'] or 'inf'} "
          f"declined={ss['declined_rounds']} starved={ss['starved']}")
    if eng.chunked:
        print(f"chunked prefill: chunk={eng.chunk} budget="
              f"{eng.chunk_budget} tok/turn, {ss['chunk_calls']} chunk "
              f"calls over {ss['chunk_tokens']} tokens"
              + (f", prefill shards 0..{eng.prefill_shards - 1} of "
                 f"{eng.dp}" if eng.prefill_shards else ""))
    if eng.paged:
        s = eng.kv_stats()
        print(f"paged kv [{s['kv_dtype']}]: peak {s['max_in_use']}/"
              f"{s['num_pages']} pages "
              f"({s['peak_kv_bytes'] / 1e6:.2f} MB resident at peak vs "
              f"{s['dense_equiv_bytes'] / 1e6:.2f} MB dense-equivalent)")
        if "prefix_cache" in s:
            pc = s["prefix_cache"]
            print(f"prefix cache: {pc['hits']} page hits, "
                  f"{pc['hit_tokens']} prefill tokens skipped, "
                  f"{pc['bytes_saved'] / 1e6:.2f} MB KV writes saved")
        if s.get("kv_byte_budget"):
            print(f"kv byte budget: {s['kv_byte_budget'] / 1e6:.2f} MB "
                  f"ceiling, {s['budget_evictions']} budget evictions")
    if eng.arena is not None:
        a = eng.arena_stats()
        print(f"state arena [{a['state_kind']}]: peak {a['max_in_use']}/"
              f"{a['num_rows']} rows of {a['bytes_per_row'] / 1e3:.1f} kB "
              f"({a['alloc_count']} allocs, {a['sizing_stalls']} stalls)")
    if eng.image_encodes or eng.image_feat_hits:
        print(f"vision frontend: {eng.image_encodes} tower encodes, "
              f"{eng.image_feat_hits} feature-memo hits")


if __name__ == "__main__":
    main()
