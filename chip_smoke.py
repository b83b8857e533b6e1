#!/usr/bin/env python3
"""Chip smoke test: serve internvl2-2b at full width on a TPU and check it.

    python3 chip_smoke.py             # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4   # the sharded serving path, 4 chips

One chip. The engine is built by the serve CLI's own construction
(``repro.launch.serve.build_model``/``build_engine``) from CLI flags:
internvl2-2b at its published language widths in bf16, params and
448x448 images drawn from ``--seed``, ``impl=paged_pallas`` with the
prefix cache and cross-modal rescoring on, so the flash, paged-decode
and xmodal_score kernels are all on the served path.

  (a) kernel parity: each Pallas kernel at the served shapes against its
      jnp oracle in ``kernels/ref.py``, within ``TOL``; each compiled
      kernel holds ``tpu_custom_call``.
  (b) serve: 5 requests over 2 images in CAMD mode; every request
      completes, one tower encode per distinct image, at least one
      image-prefix cache hit, pages and slots conserved at the end; the
      compiled decode step holds ``tpu_custom_call``.
  (c) reference agreement: the kernel engine against the XLA ``paged``
      engine on the same greedy requests — prefill and first decode step
      logits within ``LOGIT_TOL``, top-1 tokens equal (see ``_agree``).

Four chips. The single-device engine against ``--serve-dp 4`` with the
prefix cache and ``--prefill-shards 1``, in this one process: greedy
streams must be identical, and the KV pool and per-slot state must span
all four devices.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Any failed check raises. The last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED_FLAGS = ["--arch", "internvl2-2b", "--no-reduced", "--image-pool", "2",
              "--prompt-len", "12", "--slots", "8", "--macro-steps", "8",
              "--prefix-cache"]
SERVE_FLAGS = SEED_FLAGS + ["--impl", "paged_pallas", "--mode", "camd",
                            "--requests", "5", "--max-new", "64",
                            "--xmodal-rescore"]
REF_FLAGS = SEED_FLAGS + ["--mode", "greedy", "--requests", "5",
                          "--max-new", "16"]
MESH_FLAGS = SEED_FLAGS + ["--impl", "paged", "--mode", "greedy",
                           "--requests", "4", "--max-new", "32"]

# (a) kernel vs oracle, max |kernel - oracle| <= atol + rtol * |oracle|.
# bf16 outputs carry 8 mantissa bits: 2e-2 is a few ulps at |x| <= 1.
TOL = {"bfloat16": (2e-2, 2e-2), "float32": (2e-3, 0.0)}
# (c) kernel engine vs XLA engine on bf16 logits of O(1): both sides
# round every layer to bf16 but in different orders, over 24 layers.
LOGIT_TOL = 0.25


def require(cond, what: str):
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Seconds spent in backend compiles (or persistent-cache loads)."""

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


def _custom_call_in(fn, *args) -> bool:
    """Whether the compiled program of ``fn`` holds a Pallas kernel."""
    import jax
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    return "tpu_custom_call" in lower(*args).compile().as_text()


def _maxerr(got, want):
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    require(np.isfinite(g).all(), "kernel output is finite")
    return float(np.max(np.abs(g - w))), float(np.max(np.abs(w)))


def _check(name, got, want, dtype):
    atol, rtol = TOL[dtype]
    err, scale = _maxerr(got, want)
    print(f"  {name}: max|kernel-oracle| {err:.3e} (bound "
          f"{atol + rtol * scale:.3e})")
    require(err <= atol + rtol * scale, f"{name} parity")


def phase_kernels(args, cfg, eng):
    """(a) every main-path kernel at the served shapes vs its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.models.attention import kv_quantize

    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = eng.model.param_dtype
    dname = jnp.dtype(dt).name
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed + 1), 16))
    oracle = functools.partial(jax.default_matmul_precision, "highest")

    def normal(shape, dtype=dt):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    L = args.prompt_len + cfg.num_evidence_tokens     # a cold image prefill
    q, k, v = (normal((1, L, H, hd)) for _ in range(3))
    flash = functools.partial(ops.flash_attention, causal=True)
    with oracle():
        want = jax.jit(ref.flash_attention_ref)(q, k, v)
    _check(f"flash_attention L={L}", jax.jit(flash)(q, k, v), want, dname)
    require(_custom_call_in(flash, q, k, v), "flash kernel compiled")

    B, ps, n = eng.B, eng.page_size, eng.pages_per_slot
    P = eng.pool.num_pages
    rng = np.random.default_rng(args.seed)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * n].reshape(B, n),
                     jnp.int32)
    lengths = jnp.asarray(rng.integers(L, n * ps + 1, size=B), jnp.int32)
    qd = normal((B, 1, H, hd))
    kp, vp = normal((P, ps, Hkv, hd)), normal((P, ps, Hkv, hd))
    with oracle():
        want = jax.jit(ref.paged_decode_attention_ref)(qd, kp, vp, bt,
                                                       lengths)
    _check(f"paged_decode_attention {dname} P={P} ps={ps}",
           jax.jit(ops.paged_decode_attention)(qd, kp, vp, bt, lengths),
           want, dname)
    require(_custom_call_in(ops.paged_decode_attention, qd, kp, vp, bt,
                            lengths), "paged decode kernel compiled")
    (kq, ks), (vq, vs) = kv_quantize(kp, jnp.int8), kv_quantize(vp, jnp.int8)
    with oracle():
        want = jax.jit(ref.paged_decode_attention_ref)(
            qd, kq, vq, bt, lengths, ks, vs)
    got = jax.jit(lambda *a: ops.paged_decode_attention(
        *a[:5], k_scale=a[5], v_scale=a[6]))(qd, kq, vq, bt, lengths, ks, vs)
    _check("paged_decode_attention int8", got, want, dname)

    S = eng.cache_len
    kd, vd = normal((B, S, Hkv, hd)), normal((B, S, Hkv, hd))
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    with oracle():
        want = jax.jit(ref.decode_attention_ref)(qd, kd, vd, mask)
    _check(f"decode_attention S={S}",
           jax.jit(ops.decode_attention)(qd, kd, vd, mask), want, dname)
    require(_custom_call_in(ops.decode_attention, qd, kd, vd, mask),
            "decode kernel compiled")

    d, ne = cfg.d_model, cfg.num_evidence_tokens
    toks = normal((1, args.max_new, d), jnp.float32)
    tmask = (jnp.arange(args.max_new) < args.max_new // 2)[None].astype(
        jnp.float32)
    vis = normal((1, ne, d), jnp.float32)
    txt = normal((1, args.prompt_len, d), jnp.float32)
    with oracle():
        want = jax.jit(ref.xmodal_score_ref)(toks, tmask, vis, txt)
    _check(f"xmodal_score Nv={ne} d={d}",
           jax.jit(ops.xmodal_score)(toks, tmask, vis, txt), want, "float32")
    require(_custom_call_in(ops.xmodal_score, toks, tmask, vis, txt),
            "xmodal kernel compiled")


def _distinct_images(reqs) -> int:
    return len({r.image.tobytes() for r in reqs if r.image is not None})


def _assert_conserved(eng):
    import numpy as np
    require((eng._slot_req < 0).all(), "every slot freed")
    require(eng._reserved == 0, "no page reservations left")
    eng.pool.check()
    cached = eng.pool.prefix.cached_pages if eng.pool.prefix else 0
    require(eng.pool.in_use == cached,
            f"pages in use {eng.pool.in_use} == cached {cached}")
    require(np.all(np.asarray(eng.state.active) == 0), "no slot active")


def phase_serve(args, cfg, eng, clock):
    """(b) the CAMD serve run through the kernel engine."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import make_requests

    reqs = make_requests(args, cfg)
    c0, t0 = clock.total, time.perf_counter()
    for r in reqs:
        eng.submit(r)
    results = eng.run()
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    require(len(results) == len(reqs), "every request returns a result")
    for r in results:
        require(r.n_candidates > 0 and len(r.tokens) > 0,
                f"request {r.uid} completed")
    kv = eng.kv_stats()
    pc = kv["prefix_cache"]
    n_img = _distinct_images(reqs)
    print(f"serve phase (not a benchmark): {len(reqs)} requests, "
          f"{n_img} distinct images, wall {wall:.2f} s of which "
          f"{comp:.2f} s compiling")
    print(f"  tokens generated {eng.total_tokens}, decode steps "
          f"{eng.total_steps}, macro launches {eng.macro_launches}, "
          f"candidates {sum(r.n_candidates for r in results)}")
    print(f"  vision tower encodes {eng.image_encodes}, feature-memo hits "
          f"{eng.image_feat_hits}; prefix cache hits {pc['hits']} pages "
          f"({pc['hit_tokens']} prefill tokens skipped)")
    require(eng.image_encodes == n_img, "one tower encode per image")
    require(pc["hits"] >= 1, "at least one image-prefix cache hit")
    _assert_conserved(eng)
    print(f"  conservation: pool.check ok, {eng.pool.in_use} pages held "
          f"only by the prefix cache, all {eng.B} slots free")

    frontier = jnp.zeros((eng.B, eng._frontier_width), jnp.int32)
    require(_custom_call_in(eng._macro_fn, eng.params, eng.state,
                            eng._decode_key, jnp.int32(0), eng._evid,
                            frontier),
            "the compiled decode step holds the Pallas kernel")
    print("  compiled decode step contains tpu_custom_call")


def _first_logits(eng, reqs):
    """Prefill and admit ``reqs`` as ``run`` does before its first
    launch; returns (prefill logits, live slots)."""
    import numpy as np
    for r in reqs:
        eng.submit(r)
    eng._begin()
    live = np.nonzero(eng._slot_req >= 0)[0]
    pos = np.asarray(eng.state.cache["pos"])[live]
    # a first write on a page boundary would need a frontier page the
    # launch stages; mid-page it lands in the admitted tail page
    require((pos % eng.page_size != 0).all(), "first decode is mid-page")
    pre = np.stack([np.asarray(eng._reqs[int(eng._slot_req[s])]
                               ["prefill_logits"][0]) for s in live])
    return pre, live


def _agree(name, got, want):
    """max |Δlogit| <= LOGIT_TOL, and per row the top-1 tokens are equal
    unless the reference's own top-2 margin is inside that bound (a
    near-tie either side may take)."""
    import numpy as np
    err, _ = _maxerr(got, want)
    top_g, top_w = got.argmax(-1), want.argmax(-1)
    srt = np.sort(want, axis=-1)
    margin = srt[:, -1] - srt[:, -2]
    equal = top_g == top_w
    ties = (~equal) & (margin <= LOGIT_TOL)
    print(f"  {name}: max|Δlogit| {err:.3e} (bound {LOGIT_TOL}); top-1 "
          f"equal {int(equal.sum())}/{len(equal)}, near-tie rows "
          f"{int(ties.sum())}; min top-2 margin {margin.min():.3e}")
    require(err <= LOGIT_TOL, f"{name} logits within bound")
    require(np.all(equal | ties), f"{name} top-1 tokens equal")


def phase_reference(args_k, args_x, cfg, model, params):
    """(c) kernel engine vs the XLA paged engine, same greedy requests."""
    import jax
    import numpy as np

    from repro.launch.serve import build_engine, make_requests

    engs = {"kernel": build_engine(args_k, model, params),
            "xla": build_engine(args_x, model, params)}
    pre, step_out = {}, {}
    for name, eng in engs.items():
        pre[name], live = _first_logits(eng, make_requests(args_k, cfg))
    # the same input token on both sides, so a prefill near-tie cannot
    # make the decode comparison compare different continuations
    tok = engs["xla"].state.last_token
    for name, eng in engs.items():
        step = jax.jit(functools.partial(eng.model.decode_step,
                                         impl=eng._model_impl))
        lg, _, _ = step(eng.params, tok, eng.state.cache)
        step_out[name] = np.asarray(lg, np.float32)[live]
    _agree("prefill logits", pre["kernel"], pre["xla"])
    _agree("first decode step logits", step_out["kernel"], step_out["xla"])
    res = {name: {r.uid: r.tokens.tolist() for r in eng.run()}
           for name, eng in engs.items()}
    same = sum(res["kernel"][u] == res["xla"][u] for u in res["xla"])
    print(f"  full greedy streams identical: {same}/{len(res['xla'])} "
          f"(information; a near-tie may fork a stream)")
    for eng in engs.values():
        _assert_conserved(eng)


def run_one_chip(seed: int, clock: CompileClock):
    import jax

    from repro.launch.serve import build_engine, build_model, build_parser

    parse = build_parser().parse_args
    args = parse(SERVE_FLAGS + ["--seed", str(seed)])
    t0, c0 = time.perf_counter(), clock.total
    cfg, model, params = build_model(args)
    jax.block_until_ready(params)
    eng = build_engine(args, model, params)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, dtype "
          f"{jax.numpy.dtype(model.param_dtype).name}, image tokens "
          f"{cfg.num_evidence_tokens}, cache_len {eng.cache_len}, pool "
          f"{eng.pool.num_pages} pages of {eng.page_size}")
    print(f"set-up: {time.perf_counter() - t0:.2f} s building params and "
          f"engine ({clock.total - c0:.2f} s compiling)")

    print("phase (a): kernel parity at the served shapes")
    c0 = clock.total
    phase_kernels(args, cfg, eng)
    print(f"  ({clock.total - c0:.2f} s compiling)")
    print("phase (b): serve")
    phase_serve(args, cfg, eng, clock)
    del eng
    print("phase (c): kernel engine vs XLA paged engine")
    c0 = clock.total
    phase_reference(parse(REF_FLAGS + ["--impl", "paged_pallas",
                                       "--seed", str(seed)]),
                    parse(REF_FLAGS + ["--impl", "paged",
                                       "--seed", str(seed)]),
                    cfg, model, params)
    print(f"  ({clock.total - c0:.2f} s compiling)")


def run_four_chips(seed: int):
    import jax

    from repro.launch.serve import (build_engine, build_model, build_parser,
                                    make_requests)

    require(len(jax.devices()) >= 4, "four devices")
    parse = build_parser().parse_args
    args1 = parse(MESH_FLAGS + ["--seed", str(seed)])
    args4 = parse(MESH_FLAGS + ["--seed", str(seed), "--serve-dp", "4",
                                "--prefill-shards", "1"])
    cfg, model, params = build_model(args1)
    streams = {}
    for name, args in (("single", args1), ("dp4", args4)):
        eng = build_engine(args, model, params)
        reqs = make_requests(args, cfg)
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        streams[name] = {r.uid: r.tokens.tolist() for r in eng.run()}
        print(f"{name}: {len(reqs)} greedy requests, {eng.total_tokens} "
              f"tokens, {eng.macro_launches} macro launches, wall "
              f"{time.perf_counter() - t0:.2f} s incl. compiles (not a "
              f"benchmark); prefix cache hits "
              f"{eng.kv_stats()['prefix_cache']['hits']} pages")
        _assert_conserved(eng)
        if name == "dp4":
            st = eng.state
            pools = {len(e["k_pages"].sharding.device_set)
                     for e in st.cache["super"] + st.cache["tail"]}
            slot_leaves = (st.last_token, st.cache["pos"],
                           st.cache["block_table"], st.out_buf, st.active)
            per_slot = {len(x.sharding.device_set) for x in slot_leaves}
            print(f"  devices holding k_pages: {sorted(pools)}; holding "
                  f"per-slot state: {sorted(per_slot)}")
            require(pools == {4} and per_slot == {4},
                    "KV pool and slot state span 4 devices")
        del eng
    same = sum(streams["single"][u] == streams["dp4"][u]
               for u in streams["single"])
    print(f"greedy streams identical, single device vs serve-dp 4: "
          f"{same}/{len(streams['single'])}")
    require(streams["single"] == streams["dp4"], "sharded streams identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, serve, reference (default); "
                         "4: the sharded serving path only")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); this "
              "script only runs on the chip", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device_kind {devs[0].device_kind}, device count {len(devs)}, "
          f"jax {jax.__version__}, compile cache {cache}")
    clock = CompileClock()
    if a.chips == 4:
        run_four_chips(a.seed)
    else:
        run_one_chip(a.seed, clock)
    stats = devs[0].memory_stats() or {}
    print(f"compile seconds (set-up) {clock.total:.2f}; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
