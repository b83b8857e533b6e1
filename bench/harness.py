"""One run of one cell: build the system under test from the cell's
configuration file, warm every shape its traffic uses, drive the
program's async front end for the measured window with the traffic
mix, read the counters (and, with ``trace``, the profiler), free the
program, and compare what it served with the plain reference.

Everything particular to a configuration, a traffic mix or a per-layer
metric lives in a file of its own that is found by name:
``configs/<config>.json``, ``traffic/<mix>.json`` (whose generator kinds
are modules under ``gen/``), ``limits/<cell>.json``,
``metrics/<metric>.py`` and the configuration's plain reference
``reference/<name>.py``. A mix with an ``images`` group gives every
request an image (``images.py``): the program's vision tower encodes it,
and the reference is handed the same pixels.

Traffic is a closed loop: ``clients`` callers, each sending its next
request once the previous one is answered. The window opens after a
fixed number of decode steps, once every slot is busy and the first
requests have been answered and replaced; set-up ends where the warm-up
ends, before the first caller sends.
"""
from __future__ import annotations

import asyncio
import gc
import importlib
import importlib.util
import json
import logging
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import images

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---------------------------------------------------------------------------
# cell description
# ---------------------------------------------------------------------------

def load_cell(name: str, bench_json: Optional[Path] = None) -> Dict[str, Any]:
    spec = json.loads((bench_json or ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "mix": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                          .read_text()),
        "limits": json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def gen(kind: str):
    return importlib.import_module(f"bench.gen.{kind}")


def serve_flags(config: dict, mix: dict, seed: int) -> List[str]:
    lengths = mix["prompt_len"]
    flags = list(config["serve_flags"]) + [
        "--mode", mix["mode"], "--max-new", str(mix["max_new"]),
        "--prompt-len", str(max(gen(lengths["kind"]).support(lengths))),
        "--seed", str(seed & 0x7FFFFFFF)]
    return flags + list(mix.get("serve_flags", []))


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

class Traffic:
    """The mix's requests, drawn from the seed: a fixed multiset of
    prompt lengths in an order drawn from the seed, the prompts, and
    with an ``images`` group each request's image id (its pixels at
    the ``vision`` sizes, drawn when the request is made)."""

    def __init__(self, mix: dict, vocab: int, seed: int,
                 vision: Optional[dict] = None):
        self.vocab, self.seed, self.vision = vocab, seed, vision
        rng = np.random.default_rng([seed, 1])
        self.n = mix["arrivals"]["requests"]
        lk = mix["prompt_len"]
        self.lengths = gen(lk["kind"]).draw(lk, self.n, rng)
        self.image_ids = None
        if "images" in mix:
            if vision is None:
                raise SystemExit("a mix with images needs the "
                                 "configuration's sizes.vision")
            self.image_ids = images.ids(mix["images"], self.n, seed)

    def prompt(self, i: int, length: Optional[int] = None) -> np.ndarray:
        r = np.random.default_rng([self.seed, 2, i])
        L = int(self.lengths[i]) if length is None else length
        return r.integers(2, self.vocab, size=L).astype(np.int32)

    def image_of(self, i: int) -> Optional[int]:
        return None if self.image_ids is None else int(self.image_ids[i])

    def request(self, i: int, uid: int, length: Optional[int] = None,
                image: Optional[int] = None):
        """Request ``i`` under ``uid``; its image is ``image`` (an id)
        or, by default, the one the mix gives request ``i``."""
        from repro.serving import Request
        image = self.image_of(i) if image is None else image
        pixels = None if image is None else \
            images.pixels(self.seed, image, self.vision)
        return Request(uid=uid, prompt=self.prompt(i, length), image=pixels)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class CompileClock(logging.Handler):
    """Backend compiles (and persistent-cache loads): count and seconds;
    while ``names`` is a list, the programs JAX lowers are named in it."""

    def __init__(self):
        import jax
        super().__init__(logging.DEBUG)
        self.total, self.count = 0.0, 0
        self.names: Optional[List[str]] = None

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += duration
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
        # the lowering log names each program that misses the in-memory
        # cache; kept here, its warnings still reach standard error
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record):
        msg = record.getMessage()
        if self.names is not None and msg.startswith("Compiling "):
            self.names.append(msg[10:])
        elif record.levelno >= logging.WARNING:
            print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# recorder (trace runs only): what each launch and prefill computed
# ---------------------------------------------------------------------------

class Recorder:
    """Wraps the engine's decode launch and bucketed prefill to record,
    from the host's own bookkeeping (no device read), the work they were
    given: the live slots' contexts per launch, and the prompt lengths
    of each prefill."""

    def __init__(self, eng):
        self.on = False
        self.launches: List[tuple] = []   # (contexts, device step count)
        self.prefills: List[int] = []     # prompt tokens prefilled
        self.slots = eng.B
        macro = eng._macro_fn

        def launch(params, st, *a):
            out = macro(params, st, *a)
            if self.on:
                live = eng._slot_req >= 0
                self.launches.append((eng._slot_pos[live].copy(), out[2]))
            return out
        eng._macro_fn = launch
        bucket = eng._prefill_bucket

        def prefill(Lb, ne, reqs):
            if self.on:
                self.prefills += [len(r.prompt) + ne for r in reqs]
            return bucket(Lb, ne, reqs)
        eng._prefill_bucket = prefill

    def decode_contexts(self) -> List[np.ndarray]:
        """Per decode step, the key counts of the slots it advanced."""
        out = []
        for pos, steps in self.launches:
            for i in range(int(np.asarray(steps))):
                out.append(pos + i + 1)
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Run:
    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, trace_seconds: float = 8.0):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.trace, self.t_start = trace, t_start
        self.trace_seconds = min(trace_seconds, seconds)
        self.config, self.mix = spec["config"], spec["mix"]

    @staticmethod
    def say(msg: str):
        print(msg, file=sys.stderr, flush=True)

    # -- build ------------------------------------------------------------
    def build(self):
        import jax
        from repro.launch.serve import build_engine, build_model, build_parser

        from bench.weights import make_params
        args = build_parser().parse_args(
            serve_flags(self.config, self.mix, self.seed))
        box = {}

        def layout():
            cfg, model, params = build_model(args)
            box["cfg"], box["model"] = cfg, model
            return params
        shapes = jax.eval_shape(layout)
        self.cfg, self.model = box["cfg"], box["model"]
        self._check_sizes()
        self.params = make_params(shapes, self.seed, self.config.get("init"))
        jax.block_until_ready(self.params)
        self.eng = build_engine(args, self.model, self.params)

    def _check_sizes(self):
        """The configuration file's sizes are what the program runs.
        Where the file has ``sizes.vision``, each of its keys is the
        program's ``cfg.vision`` field of that name, and the evidence
        span (``num_evidence_tokens`` x ``evidence_dim``) is the
        program's too; ``tokens`` and ``projector`` only describe the
        tower's work to ``costs.vision_encode_flops``."""
        c, s = self.cfg, self.config["sizes"]
        ran = {"num_layers": c.num_layers, "d_model": c.d_model,
               "num_heads": c.num_heads, "num_kv_heads": c.num_kv_heads,
               "head_dim": c.resolved_head_dim, "d_ff": c.d_ff,
               "vocab_size": c.vocab_size, "qk_norm": c.qk_norm,
               "rope_theta": c.rope_theta, "norm_eps": c.norm_eps,
               "tie_embeddings": c.tie_embeddings, "mlp": c.mlp_activation}
        declared = {k: s.get(k) for k in ran}
        if "vision" in s:
            for k in ("num_evidence_tokens", "evidence_dim"):
                ran[k], declared[k] = getattr(c, k), s.get(k)
            for k, want in s["vision"].items():
                if k not in ("tokens", "projector"):
                    ran[f"vision.{k}"] = getattr(c.vision, k, "no such field")
                    declared[f"vision.{k}"] = want
        diff = {k: (declared[k], v) for k, v in ran.items()
                if declared[k] != v}
        if diff:
            raise SystemExit(f"configuration sizes differ from the program's "
                             f"(file, program): {diff}")

    # -- warm-up ----------------------------------------------------------
    async def _serve_batch(self, fe, reqs, cancel_after_prefill=False):
        """Submit ``reqs`` together; wait for all (or cancel them once
        they have been prefilled)."""
        for r in reqs:
            await fe.submit(r)
        if cancel_after_prefill:
            while any(r.uid not in self.eng._reqs for r in reqs):
                await asyncio.sleep(0.001)
            for r in reqs:
                await fe.cancel(r.uid)
        for r in reqs:
            await fe.result(r.uid)

    async def warm(self, fe, traffic: Traffic):
        """Every shape the mix can reach: a prompt per seeded page shape
        (full pages, partial tail); every prefill length bucket at every
        row count up to ``warm_rows``; every count of candidates, up to
        ``warm_finish``, finishing in one launch (and so every count of
        requests whose rounds end together); a request admitted with
        each count of candidates short of a round (when fewer slots are
        free); and the first-token sampler of a later round, which adds
        the round's guidance bias. A mix with images sends an image of
        its own with every warm-up request (so the page shapes count
        the evidence span ahead of the prompt), and walks
        ``_warm_images`` besides."""
        mix, eng = self.mix, self.eng
        support = gen(mix["prompt_len"]["kind"]).support(mix["prompt_len"])
        self.uid, self.warm_images = 10 ** 9, 0
        ps, per = eng.page_size, eng._per_round()
        ne = self.cfg.num_evidence_tokens if traffic.image_ids is not None \
            else 0
        clock, spent = time.perf_counter, {}
        t = clock()
        # a prompt for each count of full pages (with a partial tail
        # where the support has one), a slotful of requests at a time
        reps: Dict[int, int] = {}
        for L in support:
            full, tail = divmod(L + ne, ps)
            if full not in reps or tail:
                reps.setdefault(full, L)
                if tail and (reps[full] + ne) % ps == 0:
                    reps[full] = L
        lengths = sorted(reps.values())
        for i in range(0, len(lengths), eng.B // per):
            await self._serve_batch(
                fe, [self._warm_request(traffic, L)
                     for L in lengths[i:i + eng.B // per]],
                cancel_after_prefill=True)
        spent["pages"], t = clock() - t, clock()
        if ne:
            await self._warm_images(fe, traffic, support)
            spent["images"], t = clock() - t, clock()
        # every bucket at every row count, buckets sharing a batch
        buckets: Dict[int, List[int]] = {}
        for L in support:
            b = max(eng.prefill_bucket_min, 1 << (L - 1).bit_length())
            buckets.setdefault(b, []).append(L)
        rows = [1 << k for k in range(8) if (1 << k) <= mix["warm_rows"]]
        for batch in _pack([(b, nb) for b in buckets for nb in rows],
                           mix["warm_rows"]):
            await self._serve_batch(
                fe, [self._warm_request(traffic, buckets[b][j % len(buckets[b])])
                     for b, nb in batch for j in range(nb)],
                cancel_after_prefill=True)
        spent["prefill rows"], t = clock() - t, clock()
        L = min((L for L in support if (L + ne) % ps), default=min(support))
        for fill in _pack([(k, k) for k in range(1, mix["warm_finish"] + 1)],
                          eng.B):
            held = await self._admit(fe, traffic, L, eng.B // per)
            for k, _ in fill:
                await self._end(held, k)
            await self._end(held)
        spent["finishes"], t = clock() - t, clock()
        for n in range(1, per):
            # every slot busy, one request queued; n slots then come free
            held = await self._admit(fe, traffic, L, eng.B // per)
            queued = await self._admit(fe, traffic, L, 1, wait=False)
            await self._end(held, n)
            await self._admit(fe, traffic, L, 0, queued)
            held.update(queued)
            await self._end(held)
        spent["partial admissions"] = clock() - t
        self._warm_biased_first(per)
        eng.reset_stats()       # the closed loop counts steps from here
        self.say(f"warm-up: {self.uid - 10 ** 9} requests over "
                 f"{len(support)} prompt lengths; seconds: " + ", ".join(
                     f"{k} {v:.1f}" for k, v in spent.items()))

    async def _warm_images(self, fe, traffic: Traffic, support: list):
        """An image request at every prompt length, each with a fresh
        image: the tower, the prefill of the evidence span, and the host
        ops of an image request's set-up, which compile per prompt
        length. Then the same images again under new prompts: the
        feature memo's hit and, with the prefix cache on, the prefill
        over the cached image pages at every suffix length."""
        eng = self.eng
        step = eng.B // eng._per_round()
        for i in range(0, len(support), step):
            batch = [(L, self._fresh_image()) for L in support[i:i + step]]
            for _ in range(2 if eng.prefix_cache or i == 0 else 1):
                await self._serve_batch(
                    fe, [self._warm_request(traffic, L, g) for L, g in batch],
                    cancel_after_prefill=True)

    def _fresh_image(self) -> int:
        self.warm_images += 1
        return images.WARM_ID0 + self.warm_images

    def _warm_request(self, traffic: Traffic, length: int,
                      image: Optional[int] = None):
        """A warm-up request of ``length`` prompt tokens. In a mix with
        images it carries ``image`` (by default a fresh one, outside
        every pool) and a prompt of its own, so that no two warm-up
        requests share a cached page the window's would not."""
        if traffic.image_ids is None:
            req = traffic.request(0, self.uid, length=length)
        else:
            req = traffic.request(self.uid, self.uid, length=length,
                                  image=self._fresh_image() if image is None
                                  else image)
        self.uid += 1
        return req

    async def _admit(self, fe, traffic: Traffic, length: int, count: int,
                     held: Optional[Dict[int, Any]] = None,
                     wait: bool = True) -> Dict[int, Any]:
        """Submit ``count`` requests together (adding them to ``held``)
        and, with ``wait``, wait until each in ``held`` has been admitted
        (with as many candidates as slots were free) or has finished;
        their results, by uid."""
        eng = self.eng
        reqs = [self._warm_request(traffic, length) for _ in range(count)]
        held = {} if held is None else held
        for r in reqs:
            await fe.submit(r)
            held[r.uid] = asyncio.ensure_future(fe.result(r.uid))
        for uid, fut in held.items():
            while wait and not fut.done() and \
                    not eng._reqs.get(uid, {}).get("cand_slots"):
                await asyncio.sleep(0.001)
        return held

    async def _end(self, held: Dict[int, Any], k: Optional[int] = None):
        """Through the engine's own finish path: lower the device-side
        token limit of ``k`` live slots of the ``held`` requests so that
        they end at the next step, and wait for them. Without ``k``,
        end every slot they hold, and any later round they are given,
        until all have their results."""
        import jax.numpy as jnp
        eng = self.eng
        while True:
            live = [s for s in range(eng.B) if eng._slot_req[s] in held]
            if not live:
                if k is not None or all(f.done() for f in held.values()):
                    return
                await asyncio.sleep(0.001)
                continue
            end = live if k is None else live[:k]
            cand = {s: eng._slot_cand[s] for s in end}
            st = eng.state
            eng.state = st._replace(
                limit=st.limit.at[jnp.asarray(end)].set(1))
            while any(eng._slot_cand[s] == c for s, c in cand.items()):
                await asyncio.sleep(0.001)
            if k is not None:
                return

    def _warm_biased_first(self, per: int):
        """The first-token sampler as a later round calls it, with the
        guidance bias, for every count of candidates a round admits."""
        import jax
        import jax.numpy as jnp
        eng = self.eng
        row = jnp.zeros((1, eng.V), jnp.float32)
        for n in range(1, per + 1):
            keys = jax.random.split(eng.key, n)
            jax.block_until_ready(
                eng._first_fn(keys, row, row, eng._greedy_row))

    # -- serving ----------------------------------------------------------
    async def drive(self):
        """Warm up, then serve the closed loop through the window."""
        import jax
        from repro.serving.frontend import AsyncServeFrontend

        eng, mix = self.eng, self.mix
        self.traffic = traffic = Traffic(mix, self.cfg.vocab_size, self.seed,
                                         self.config["sizes"].get("vision"))
        self.rec = Recorder(eng) if self.trace else None
        # the candidates of each request's first admission: sampled with
        # no guidance bias, so the reference can follow them
        self.first_round: Dict[int, range] = {}
        admit = eng._admit

        def first_round(req, slot_ids, limit=None):
            c0 = eng._next_cand
            admit(req, slot_ids, limit)
            self.first_round.setdefault(req.uid, range(c0, eng._next_cand))
        eng._admit = first_round
        orig_pump = eng.pump
        clock = time.perf_counter
        # inside the window: pumps over a second (with the time of each
        # engine phase inside them) and garbage-collector passes, to tell
        # a stall's cause
        self.stalls: Optional[Dict[str, list]] = None

        def pump():
            t = clock()
            with jax.profiler.TraceAnnotation("pump"):
                out = orig_pump()
            if self.stalls is not None and clock() - t > 1.0:
                self.stalls["pumps"].append(
                    (round(t - self.window["t0_abs"], 3), round(clock() - t, 3)))
                last = eng.span_stats()["last_pump"] or {"children": {}}
                self.stalls["phases"].append(
                    {k: round(ns * 1e-9, 3)
                     for k, ns in last["children"].items()})
            return out
        eng.pump = pump

        def collected(phase, info):
            if self.stalls is None:
                return
            if phase == "start":
                self._gc_t = clock()
            elif hasattr(self, "_gc_t"):
                self.stalls["gc"].append(clock() - self._gc_t)
        gc.callbacks.append(collected)
        fe = AsyncServeFrontend(eng, stream_tokens=False)
        await fe.start()
        progress = asyncio.ensure_future(self._progress(clock))
        await self.warm(fe, traffic)
        self.window = {"setup_s": clock() - self.t_start}
        self.traces: Dict[int, dict] = {}
        self.finished: List[dict] = []
        t_lead = clock()
        await self._closed(fe, clock)
        progress.cancel()
        gc.callbacks.remove(collected)
        await fe.close()
        self.say(f"set-up {self.window['setup_s']:.2f} s, lead-in "
                 f"{self.window['t0_abs'] - t_lead:.2f} s")

    async def _one(self, fe, i: int):
        """One request: submitted, awaited, its candidates kept."""
        import jax
        clock = time.perf_counter
        req = self.traffic.request(i, i)
        with jax.profiler.TraceAnnotation("submit"):
            await fe.submit(req)
        tr = {"t_submit": clock()}
        self.traces[i] = tr
        res = await fe.result(i)
        tr["t_done"] = clock()
        first = self.first_round.pop(i)
        cands = [{"tokens": np.asarray(c["tokens"]), "sum_lp": c["sum_lp"]}
                 for c in res.candidates if c["uid"] in first]
        self.finished.append({"i": i, "prompt": req.prompt,
                              "image": self.traffic.image_of(i),
                              "cands": cands, "t_done": tr["t_done"]})

    async def _progress(self, clock, every: float = 5.0):
        """A line on standard error every ``every`` seconds: decode steps,
        launches, finished tokens, busy slots, queue, free pages, device
        bytes in use."""
        eng, t = self.eng, clock()
        dev = eng.params["embed"]["table"].devices().pop()
        while True:
            await asyncio.sleep(every)
            mem = (dev.memory_stats() or {}).get("bytes_in_use")
            self.say(f"progress {clock() - t:.1f}s: steps {eng.total_steps} "
                     f"launches {eng.macro_launches} tokens "
                     f"{eng.total_tokens} busy slots "
                     f"{int((eng._slot_req >= 0).sum())} queued "
                     f"{len(eng._queue)} free pages {eng.pool.free_pages} "
                     f"bytes {mem}")

    async def _window(self, clock):
        """Open the window now: counters reset, live tokens read,
        profiler started; close it ``seconds`` later."""
        import jax
        eng = self.eng
        t0 = clock()
        self.window["t0_abs"] = t0
        self.window["compiles0"] = self.clock.count
        self.clock.names = []
        self.stalls = {"pumps": [], "phases": [], "gc": []}
        eng.reset_stats()
        self.window["live0"] = self._live_tokens()
        if self.trace:
            import tempfile
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            from bench.trace_reduce import profile_options
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
            self.rec.on = True
            self.window["trace_sched0"] = eng.sched_stats()
            with jax.profiler.TraceAnnotation("window"):
                await asyncio.sleep(max(0.0, t0 + self.trace_seconds - clock()))
                self.window["trace_s"] = clock() - t0
                self.window["trace_counters"] = self._counters()
            self.rec.on = False
            jax.profiler.stop_trace()
        await asyncio.sleep(max(0.0, t0 + self.seconds - clock()))
        self.window["t1"] = clock() - t0
        self.window["counters"] = self._counters()
        self.window["compiles"] = self.clock.count - self.window["compiles0"]
        self.window["compiled"] = self.clock.names
        self.window["stalls"] = self.stalls
        self.clock.names = self.stalls = None
        stats = self.eng.params["embed"]["table"].devices().pop().memory_stats()
        self.window["memory_peak_bytes"] = (stats or {}).get(
            "peak_bytes_in_use")

    def _live_tokens(self) -> int:
        import jax
        n, act = jax.device_get((self.eng.state.n_tok, self.eng.state.active))
        return int(n[act].sum())

    def _counters(self) -> dict:
        e = self.eng
        return {"steps": e.total_steps, "launches": e.macro_launches,
                "tokens": e.total_tokens + self._live_tokens(),
                "sched": e.sched_stats(), "slots": e.B, "kv": e.kv_stats(),
                "spans": e.span_stats()}

    async def _closed(self, fe, clock):
        arr = self.mix["arrivals"]
        mod = gen(arr["kind"])
        eng = self.eng
        nxt = iter(range(self.traffic.n))
        stop = asyncio.Event()

        async def client(c: int):
            while eng.total_steps < mod.start_step(arr, c):
                await asyncio.sleep(0.005)
            while not stop.is_set():
                await self._one(fe, next(nxt))

        tasks = [asyncio.ensure_future(client(c))
                 for c in range(arr["clients"])]
        while eng.total_steps < mod.window_step(arr):
            await asyncio.sleep(0.005)
            if any(t.done() for t in tasks):
                await _stop(tasks)      # a client failed: surface it
        await self._window(clock)
        # requests this long finish after the window: keep serving (the
        # callers stop sending) until enough have finished to compare
        stop.set()
        t0 = self.window["t0_abs"]
        cap = clock() + self.mix["drain_s"]
        while sum(r["t_done"] >= t0 for r in self.finished) \
                < self.mix["check_requests"] \
                and clock() < cap and not all(t.done() for t in tasks):
            await asyncio.sleep(0.05)
        await _stop(tasks)

    # -- after the window ---------------------------------------------------
    def window_requests(self) -> List[dict]:
        """Timelines, relative to the window's opening, of the requests
        that finished in it."""
        t0, S = self.window["t0_abs"], self.seconds
        out = []
        for tr in self.traces.values():
            r = {k: v - t0 for k, v in tr.items()}
            if 0 <= r.get("t_done", -1.0) < S:
                out.append(r)
        return out

    def context(self) -> dict:
        w = self.window
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        ctx = {"requests": self.window_requests(), "window_s": w["t1"],
               "counters": w["counters"], "live0": w["live0"],
               "setup_s": w["setup_s"], "sizes": self.config["sizes"],
               "peak": peaks[self.device_kind], "trace": None}
        if self.trace:
            from bench import trace_reduce
            pd = trace_reduce.load(self.trace_dir)
            ctx["trace"] = None if pd is None else trace_reduce.reduce(
                pd, self.config["kernels"])
            s0, s1 = w["trace_sched0"], w["trace_counters"]["sched"]
            ctx.update(trace_s=w["trace_s"],
                       trace_counters=w["trace_counters"],
                       trace_sched={k: v - s0[k] for k, v in s1.items()
                                    if isinstance(v, (int, float))},
                       decode_ctxs=self.rec.decode_contexts(),
                       prefills=self.rec.prefills, slots=self.rec.slots)
            import shutil
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return ctx


def _pack(items: List[tuple], cap: int) -> List[List[tuple]]:
    """``(key, size)`` items packed, largest first, into as few batches
    of at most ``cap`` as hold them, no key twice in a batch."""
    bins: List[List[tuple]] = []
    for key, size in sorted(items, key=lambda x: -x[1]):
        b = next((b for b in bins if sum(n for _, n in b) + size <= cap
                  and all(k != key for k, _ in b)), None)
        if b is None:
            bins.append([(key, size)])
        else:
            b.append((key, size))
    return bins


async def _stop(tasks):
    """Cancel what is still running; re-raise the first failure (a
    failed pump fails every request it holds)."""
    for t in tasks:
        t.cancel()
    for r in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(r, Exception):
            raise r


def read_metric(name: str, ctx: dict):
    """The reader ``metrics/<name>.py``: (value or None, unit)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx), mod.UNIT


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            t_start: float, device_kind: str, count: int,
            controls=()) -> dict:
    """One run: the result line's fields, and under ``check`` the
    comparison with the reference (and, for each precision named in
    ``controls``, the control's numbers on the same sample, under
    ``control``)."""
    import jax

    run = Run(spec, seed, seconds, trace, t_start)
    run.device_kind = device_kind
    run.clock = CompileClock()
    run.build()
    asyncio.run(run.drive())
    w = run.window
    run.say(f"window: {w['t1']:.3f} s, compiles inside it: {w['compiles']}")
    for name in w["compiled"][:20]:
        run.say(f"compiled inside the window: {name[:160]}")
    st = w["stalls"]
    run.say(f"inside the window: {len(st['gc'])} garbage-collector passes, "
            f"longest {max(st['gc'], default=0.0):.3f} s, total "
            f"{sum(st['gc']):.3f} s; pumps over 1 s (opened at, took): "
            f"{st['pumps']}")
    run.say(f"inside the window: engine phases of each pump over 1 s "
            f"(seconds): {st['phases']}")
    sc = w["counters"]["sched"]
    run.say(f"inside the window: {sc['prefill_calls']} prefill calls over "
            f"{sc['prefill_tokens']} tokens, {sc['image_encodes']} image "
            f"encodes, {sc['image_feat_hits']} feature-memo hits, prefix "
            f"cache {w['counters']['kv'].get('prefix_cache')}")
    ctx = run.context()
    names = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in names:
        val, unit = read_metric(m["name"], ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": unit}
    device = {"platform": jax.devices()[0].platform, "kind": device_kind,
              "count": count, "memory_peak_bytes": w["memory_peak_bytes"]}
    out = {"attempted": len(ctx["requests"]), "failed": 0,
           "metrics": metrics, "device": device}
    if trace and ctx["trace"] is not None:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    # free the program before the reference runs
    sample_from = [r for r in run.finished if r["t_done"] >= w["t0_abs"]]
    params = run.params
    run.eng = run.rec = run.model = None
    del run
    gc.collect()
    out["check"] = judge(spec, seed, params, sample_from,
                         {"window_compiles": float(w["compiles"])})
    out["control"] = {q: judge(spec, seed, params, sample_from,
                               {"window_compiles": float(w["compiles"])},
                               quant=q) for q in controls}
    return out


def judge(spec, seed, params, finished, extra, quant=None) -> dict:
    """Compare a sample of what was served with the reference; the
    numbers (with ``extra`` ones), their limits and the verdict."""
    from bench import check
    mix, config = spec["mix"], spec["config"]
    rng = np.random.default_rng([seed, 4])
    sample = check.pick_sample(finished, mix["check_requests"], rng)
    # the pixels each sampled request was served with, drawn again
    sample = [r if r.get("image") is None else dict(
        r, pixels=images.pixels(seed, r["image"], config["sizes"]["vision"]))
        for r in sample]
    numbers = check.compare(params, config, mix["sampling"], sample, quant)
    numbers.update(extra)
    ok, lines = check.verdict(numbers, spec["limits"], list(spec["limits"]))
    ok &= len(sample) == mix["check_requests"]
    return {"correct": ok, "numbers": numbers, "lines": lines,
            "sampled": len(sample),
            "tokens": int(sum(len(c["tokens"]) for r in sample
                              for c in r["cands"]))}
