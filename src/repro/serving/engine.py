"""Slot-scheduled batched serving engine with CAMD adaptive decoding.

Execution model (DESIGN.md §3): a fixed-size decode batch of ``slots``.
Each slot holds one *candidate* generation of some request. CAMD's
adaptive allocation — more samples for hard requests, fewer for easy —
falls out of slot scheduling: when a request reaches coverage its slots
are freed and refilled from the queue, so the batch never decodes padding.

The decode hot path is a device-resident **macro-step**: one jitted call
runs up to ``macro_steps`` decode+sample+CAMD-aggregate steps inside a
``jax.lax.while_loop`` (the "outer while" serving idiom), early-exiting
the moment any slot finishes so the host can fold the round. The host
regains control only at candidate-completion / round boundaries — host
synchronizations per generated token drop from ~1 (per-token loop) to
O(1/macro_steps), which is what keeps dispatch latency off the hot path.

Paged KV works inside the fused loop through *pre-staged page frontiers*:
before each launch the host reserves every live slot's next
⌈K/page_size⌉+1 pages from the ``PagePool`` into a ``(B, F)`` frontier
array, and the device advances ``block_table`` itself as slots cross page
boundaries. Unconsumed frontier pages are returned after the macro-step,
so pool accounting stays exact.

Per-step sampling keys are *folded* from one base key and the global step
index (``samplers.decode_step_key``), so the token stream is independent
of how many steps each launch covers — ``macro_steps=1`` and
``macro_steps=32`` decode bit-identical tokens. ``macro_steps=0``
preserves the legacy per-token host loop for benchmarking.

Prefill is length-bucketed: queued prompts are right-padded to
power-of-two buckets and prefilled in one batched call per bucket
(attention-only architectures; recurrent archs fall back to per-request
prefill because pads would leak into their state).

Modes: "camd" (adaptive), "best_of_n", "self_consistency", "greedy" —
the paper's baselines share the engine so efficiency comparisons are
apples-to-apples.

The engine scales past one device by sharding over a
``jax.sharding.Mesh`` (``mesh=``): the decode batch and every per-slot
``EngineState`` leaf shard on the mesh's "data" axis, the paged KV pool
shards on the *page* axis with shard boundaries matching the host
allocator's per-shard page-id ranges (``PagePool(num_shards=dp)``), and
params replicate (or reuse the training tensor-parallel rules when the
mesh carries a real "model" axis). Slots partition contiguously across
data shards; a slot's tail, frontier, and decode pages always come from
its own shard's subpool, so the fused macro-step's block-table advance
and KV scatter/gather stay shard-local. Admission control is therefore
shard-local too: ``_paged_affordable`` walks the exact slots an
admission would occupy and funds each candidate from its slot's shard.
Decode numerics and sampling are sharding-invariant, so token streams
are bit-identical to the single-device engine whenever pool capacity
does not bind (pinned by ``tests/test_serving_sharded.py`` under forced
host devices); under pool pressure, shard-local capacity can queue a
request a single global pool would have admitted — deliberate: that is
the accounting the page-axis sharding requires — which reorders
admissions rather than corrupting any stream.

Traffic-level decisions (which queued request or pending round gets the
free slots, with how many candidates and what per-candidate token limit)
are delegated to a pluggable scheduler (``serving/scheduler.py``):
``fifo`` reproduces the historical loop bit-exactly; ``coverage`` ranks
work by posterior coverage deficit + expected marginal gain under an
optional stream-wide token budget. The paged path can additionally
share page-aligned prompt prefixes across requests (``prefix_cache=True``,
``PagePool``'s content-hash chain): hits skip the shared pages' prefill
entirely via ``Model.prefill_suffix`` against the cached pages' KV.
"""
from __future__ import annotations

import dataclasses
import hashlib as _hashlib
import time
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (ATTN, LOCAL_ATTN, CAMDConfig, PagedKVConfig,
                          SamplingConfig)
from repro.core import controller as ctrl
from repro.models import attention as attn_lib
from repro.models.model import Model
from repro.sampling.samplers import (decode_step_key, sample_token,
                                     sample_token_batch, speculative_accept)
from repro.serving.page_pool import PagePool, prefix_page_keys
from repro.serving.scheduler import (NewWork, PrefillWork, RoundWork,
                                     SchedulerContext, make_scheduler)
from repro.serving.spans import Spans
from repro.serving.state_arena import StateArena


# ---------------------------------------------------------------------------
# Requests / results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                      # (L,) int32
    evidence: Optional[np.ndarray] = None   # (Ne, De) frontend embeddings
    max_new_tokens: int = 0                 # 0 => engine default
    image: Optional[np.ndarray] = None      # (H, W, C) raw image; the
                                            # engine's vision tower encodes
                                            # it into evidence at submit
                                            # (content-hash memoized)


@dataclasses.dataclass
class Result:
    uid: int
    tokens: np.ndarray                      # best candidate's generation
    n_candidates: int
    tokens_spent: int
    rounds: int
    p_star: float
    best_score: float
    stopped_early: bool
    candidates: List[Dict[str, Any]]        # per-candidate records
    cancelled: bool = False                 # aborted via ServeEngine.cancel


# ---------------------------------------------------------------------------
# Device-side engine state
# ---------------------------------------------------------------------------

class EngineState(NamedTuple):
    cache: Any
    last_token: jax.Array      # (B,)
    token_counts: jax.Array    # (B, V)
    sum_lp: jax.Array          # (B,)
    n_tok: jax.Array           # (B,) int32
    prev_h: jax.Array          # (B, d)
    sum_coh: jax.Array         # (B,)
    sum_emb: jax.Array         # (B, d)
    align_sum: jax.Array       # (B,)
    active: jax.Array          # (B,) bool
    out_buf: jax.Array         # (B, max_new)
    bias: jax.Array            # (B, V) CAMD mixture guidance
    greedy: jax.Array          # (B,) bool
    limit: jax.Array           # (B,) int32 per-candidate token limit
                               # (= max_new unless the scheduler granted a
                               # tighter budget-constrained limit)
    hist: jax.Array            # (B, H) int32 fed-token history per cache
                               # position (-1 = none/evidence) — the
                               # device-resident n-gram draft table.
                               # H = cache_len when speculation is on,
                               # 1 (dummy) otherwise
    spec_k: jax.Array          # (B,) int32 per-slot draft block length
                               # (coverage-aware; 1 = no drafting)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class ServeEngine:
    def __init__(self, model: Model, params, *, slots: int = 8,
                 cache_len: int = 512,
                 sampling: SamplingConfig = SamplingConfig(),
                 camd: CAMDConfig = CAMDConfig(),
                 mode: str = "camd",
                 n_candidates: int = 8,
                 eos_id: int = 1,
                 max_new_tokens: int = 64,
                 impl: str = "xla",
                 paged_kv: PagedKVConfig = PagedKVConfig(),
                 macro_steps: int = 8,
                 bucket_prefill: bool = True,
                 prefill_bucket_min: int = 16,
                 sched_policy="fifo",
                 global_budget: int = 0,
                 sched_kwargs: Optional[Dict[str, Any]] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: int = 0,
                 prefill_chunk_budget: int = 0,
                 prefill_shards: int = 0,
                 mesh=None,
                 spec_k: int = 0,
                 spec_mode: str = "coverage",
                 spec_ngram: int = 2,
                 xmodal_rescore: bool = False,
                 seed: int = 0):
        assert mode in ("camd", "best_of_n", "self_consistency", "greedy")
        assert impl in ("xla", "pallas", "paged", "paged_pallas")
        assert macro_steps >= 0
        # speculative decoding: draft up to spec_k-1 tokens per slot from
        # the device-resident n-gram table, verify them with ONE batched
        # block forward per loop iteration. spec_k <= 1 keeps the plain
        # one-token-per-step loop.
        assert spec_mode in ("coverage", "fixed")
        assert spec_ngram >= 1
        self.spec = spec_k > 1
        self.spec_k = spec_k if self.spec else 0
        self.spec_mode = spec_mode
        self.spec_ngram = spec_ngram
        if self.spec:
            assert macro_steps >= 1, \
                "speculative decoding runs inside the fused macro-step " \
                "loop (macro_steps >= 1)"
            assert model.supports_speculative, \
                "speculative block verification needs an all-attention " \
                "full-context decoder-only model"
        self.model, self.params = model, params
        # mesh-parallel serving: dp = product of the mesh's data axes.
        # Slots partition contiguously across the dp shards; all
        # device-side placement happens in _install_mesh below.
        self.mesh = mesh
        self.dp = 1
        if mesh is not None:
            from repro.distributed.sharding import dp_axes
            self.dp = max(1, int(np.prod(
                [mesh.shape[a] for a in dp_axes(mesh)], dtype=np.int64)))
            assert slots % self.dp == 0, \
                f"slots {slots} must divide across {self.dp} data shards"
        self.slots_per_shard = slots // self.dp
        self.cfg = model.cfg
        self.B = slots
        self.V = self.cfg.vocab_size
        self.d = self.cfg.d_model
        self.cache_len = cache_len
        self.sampling = sampling
        self.camd = camd
        self.mode = mode
        self.n_candidates = 1 if mode == "greedy" else n_candidates
        self.eos_id = eos_id
        self.max_new = max_new_tokens
        self.impl = impl
        # macro_steps K: device steps per lax.while_loop launch. 0 keeps
        # the legacy per-token host loop (one dispatch + one sync per
        # token) for A/B benchmarking against the fused path.
        self.macro_steps = macro_steps
        # paged serving: KV lives in a shared page pool; "paged" runs the
        # gather+sdpa XLA attention (bit-identical to the dense path),
        # "paged_pallas" the block-table flash-decode kernel.
        self.paged = impl.startswith("paged")
        # slot-state kind: "kv" slots own pageable KV only, "recurrent"
        # slots own fixed-size state only (SSD/RG-LRU rows), "hybrid"
        # both. Paged impls need at least one full-context attention
        # layer to page; recurrent/hybrid state is fixed-stride and is
        # managed by the StateArena below instead.
        self.state_kind = model.state_kind
        if self.paged and not model.has_pageable_layers:
            raise ValueError(
                f"impl={impl!r} pages full-context attention KV, but "
                f"{model.cfg.name} ({self.state_kind}) has no pageable "
                "layers — serve it with impl='xla'/'pallas' (fixed-stride "
                "state rows are arena-managed, not paged)")
        self._model_impl = {"paged": "xla", "paged_pallas": "pallas"}[impl] \
            if self.paged else impl
        # cross-request prefix cache: paged engines on all-attention
        # decoders only (cached pages must cover every layer's prompt KV).
        self.prefix_cache = bool(prefix_cache) and self.paged and \
            model.supports_prefix_cache
        # KV storage dtype for the paged pool. "auto" keeps the engine's
        # param dtype (historical behavior, byte-identical streams);
        # int8/fp8 pools carry per-(page, slot, kv-head) scales and
        # dequantize inside the attention kernels.
        self.kv_dtype = paged_kv.kv_dtype
        if not self.paged:
            assert self.kv_dtype == "auto", \
                f"kv_dtype={self.kv_dtype!r} needs a paged impl " \
                "(dense caches always store the param dtype)"
        if self.paged:
            # fail fast on unknown names / fp8-less jax builds
            _, self.kv_quantized = attn_lib.kv_storage_dtype(
                self.kv_dtype, model.param_dtype)
            ps = paged_kv.page_size
            assert cache_len % ps == 0, \
                f"cache_len {cache_len} must be a multiple of page_size {ps}"
            self.page_size = ps
            self.pages_per_slot = cache_len // ps
            # one quarantine page per data shard; a caller-given pool
            # size is rounded up to a shard multiple (page-axis sharding
            # needs equal subpools)
            num_pages = paged_kv.num_pages or \
                slots * self.pages_per_slot + self.dp
            if num_pages % self.dp:
                num_pages += self.dp - num_pages % self.dp
            self.pool = PagePool(num_pages, ps,
                                 prefix_cache=self.prefix_cache,
                                 num_shards=self.dp,
                                 kv_byte_budget=paged_kv.kv_byte_budget)
            self._slot_pages: List[List[int]] = [[] for _ in range(slots)]
            self._slot_pos = np.zeros(slots, np.int64)
            self._slot_limit = np.zeros(slots, np.int64)  # L + max_new
            # admission control: pages a running candidate may still
            # allocate are *reserved* at admit time, so a candidate that
            # was admitted can always finish — pool pressure surfaces as
            # queueing delay at _schedule, never as a mid-decode crash.
            # Reservations are tracked per data shard (a slot's future
            # pages can only come from its own shard's subpool).
            self._slot_reserved = np.zeros(slots, np.int64)
            self._reserved_sh = np.zeros(self.dp, np.int64)
            # frontier width: the most page boundaries one slot can cross
            # in K device steps, plus one for the boundary the first step
            # may land on. With speculation each step may commit up to
            # spec_k tokens, so the worst-case advance is K * spec_k.
            adv = max(macro_steps, 1) * max(spec_k, 1)
            self._frontier_width = min(max(1, -(-adv // ps) + 1),
                                       self.pages_per_slot)
            # chunked prefill: long prompts stream into the pool in
            # page-aligned chunks through the suffix path, interleaved
            # with decode launches so decode-bound slots keep streaming
            # behind a long prompt. Needs the suffix machinery (paged,
            # all-attention full-context decoder); any other engine
            # silently degrades to whole-prompt prefill.
            self.chunked = prefill_chunk > 0 and model.supports_prefix_cache
            self.chunk = -(-int(prefill_chunk) // ps) * ps \
                if self.chunked else 0
            self.chunk_budget = int(prefill_chunk_budget) or self.chunk
            # prefill/decode disaggregation: prompt/chunk pages are
            # placed on the first ``prefill_shards`` shards of the page
            # axis; decode slots elsewhere reference them cross-shard
            # (pages are the transfer currency — GSPMD gathers, no KV
            # copies). Tail + frontier pages stay slot-local.
            self.prefill_shards = int(prefill_shards)
            assert 0 <= self.prefill_shards <= self.dp, \
                f"prefill_shards {prefill_shards} must be in [0, dp={self.dp}]"
        else:
            self.pool = None
            self.chunked = False
            self.chunk = 0
            self.chunk_budget = 0
            assert prefill_shards == 0, \
                "prefill/decode disaggregation needs a paged impl"
            self.prefill_shards = 0
        self.key = jax.random.PRNGKey(seed)
        # decode-loop keys are folded from a dedicated base key and the
        # global step index (not split per step), so the sampled stream is
        # invariant to macro-step partitioning; self.key keeps feeding the
        # admission-time first-token sampling.
        self._decode_key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                              0x6d6163)
        self._t = 0                      # global decode step counter
        self.has_evidence = bool(self.cfg.num_evidence_tokens)
        # image frontend: submit-time vision-tower encode, memoized by
        # image content hash (bounded FIFO); the digest also keys the
        # image's pseudo-token prefix-cache stream.
        self._vision_fn = None
        self._image_feats: Dict[bytes, np.ndarray] = {}
        self._image_digest: Dict[int, bytes] = {}
        self.image_encodes = 0
        self.image_feat_hits = 0
        # evidence-weighted candidate rescoring through the fused
        # xmodal_score kernel (Eq. 8-9) instead of the running host-side
        # alignment aggregate — opt-in, recorded per candidate.
        self.xmodal_rescore = bool(xmodal_rescore) and self.has_evidence
        self._xmodal_jit = None

        self._queue: List[Request] = []
        self._slot_req = np.full(slots, -1, np.int64)   # uid per slot
        self._slot_cand = np.full(slots, -1, np.int64)  # candidate uid per slot
        self._slot_lim = np.full(slots, max_new_tokens, np.int64)
        # host mirror of per-slot draft length (frontier staging sizes
        # the worst-case advance with it)
        self._slot_spec = np.ones(slots, np.int64)
        self._reqs: Dict[int, Dict[str, Any]] = {}      # uid -> bookkeeping
        self._next_cand = 0
        self._dtype = model.param_dtype

        # traffic-level policy: every admission / round decision is
        # delegated to the scheduler (serving/scheduler.py). "fifo"
        # reproduces the pre-scheduler engine decision for decision.
        self.scheduler = make_scheduler(sched_policy,
                                        global_budget=global_budget,
                                        **(sched_kwargs or {}))
        self._arrival: Dict[int, int] = {}              # uid -> submit order
        self._submit_seq = 0
        self.starved_uids: List[int] = []               # budget-starved
        # prefill telemetry (the prefix cache exists to shrink these)
        self.prefill_calls = 0
        self.prefill_tokens = 0
        # chunked-prefill ledger: uid -> in-flight job ({"req", "pos",
        # "pages", "shard"}); requests stay queued until their final
        # chunk promotes them to _reqs, so _has_pending/cancel/starved
        # paths see them through the queue. The per-turn chunk-token
        # budget (_chunk_left) resets each _step.
        self._chunking: Dict[int, Dict[str, Any]] = {}
        self._chunk_progress = False
        self._chunk_left = self.chunk_budget
        self.chunk_calls = 0
        self.chunk_tokens = 0

        # bucketed prefill: only exact for attention-only decoders, and
        # only when the padded bucket fits every attention ring without
        # wrapping (_bucket_fits).
        self.bucket_prefill = bool(bucket_prefill) and \
            model.supports_bucketed_prefill
        self.prefill_bucket_min = prefill_bucket_min
        rings = []
        for kind in self.cfg.layer_kinds:
            if kind == ATTN:
                rings.append(cache_len if self.cfg.attn_window == 0
                             else min(cache_len, self.cfg.attn_window))
            elif kind == LOCAL_ATTN:
                rings.append(min(cache_len, self.cfg.local_window))
        self._min_ring = min(rings) if rings else cache_len

        self.state = self._blank_state()
        # fixed-stride state arena: recurrent/hybrid prompt rows live in
        # a bounded device-side buffer (model.make_cache over arena
        # rows) managed with PagePool's disciplines — per-shard free
        # lists, refcounts, conservation, telemetry — instead of the
        # unbounded per-request host dict the kv path never needed.
        self.arena = None
        self._arena_buf = None
        if self.state_kind != "kv" and not self.paged:
            per_shard = 2 * self.slots_per_shard + 4
            rows = per_shard * self.dp
            self.arena = StateArena(rows, num_shards=self.dp)
            self._arena_buf = self.model.make_cache(
                rows, self.cache_len, dtype=self._dtype)
        if self.paged:
            # the pool enforces the resident-KV byte budget itself; give
            # it the engine's bytes-per-page (values + quant scales)
            self.pool.set_bytes_per_page(self._bytes_per_page())
        self._state_sharding = None
        self._evid_sharding = None
        self._frontier_sharding = None
        if mesh is not None:
            self._install_mesh(mesh)
        self._step_body = self._make_step_body()
        # the engine state is donated into every decode launch: the host
        # always rebinds self.state to the launch's output, so XLA may
        # reuse the input buffers in place instead of copying the whole
        # KV cache + aggregates each dispatch (the paged-K8 bench
        # regression: ~4 MB of state copied per macro launch).
        self._step_fn = jax.jit(self._step_body, donate_argnums=(1,))
        self._macro_fn = self._build_macro_step_spec() if self.spec \
            else self._build_macro_step()
        self._prefill_fn = self._build_prefill()
        self._bucket_fn = self._build_bucket_prefill()
        self._first_fn = self._build_first_tokens()
        self._suffix_fn = self._build_suffix_prefill() \
            if (self.prefix_cache or self.chunked) else None
        self._greedy_row = jnp.asarray([self.mode == "greedy"])
        round_fn = ctrl.batched_round_update_assign(self.camd)

        def round_update(states, inps):
            return round_fn(states, inps)
        self._round_fn = jax.jit(round_update)
        self._dummy_frontier = jnp.zeros((slots, 1), jnp.int32)
        # telemetry: total_steps counts device decode steps;
        # macro_launches counts while_loop dispatches; host_syncs counts
        # decode-loop host<->device synchronizations (the quantity the
        # macro-step refactor exists to amortize).
        self.total_steps = 0
        self.total_tokens = 0
        self.macro_launches = 0
        self.host_syncs = 0
        # speculation telemetry: drafts proposed / drafts accepted
        self.spec_drafted = 0
        self.spec_accepted = 0
        # host-loop telemetry: the host's time from the end of one
        # launch's readback to the next launch's dispatch (gaps in which
        # the engine had no live slot and no queued work are left out),
        # and each request's wait from submit to its first admission
        self.spans = Spans()
        self.launch_gap_ns = 0
        self.launch_gaps = 0
        self.queue_wait_ns = 0
        self.first_admissions = 0
        # paged decode walk per launch, from the host's slot positions:
        # table pages holding a live token of an active slot (what the
        # kernel reads) against the table's whole width (B x pages/slot)
        self.decode_pages_live = 0
        self.decode_pages_table = 0
        self._sync_end: Optional[int] = None
        self._t_submit: Dict[int, int] = {}
        # async front-end plumbing: opt-in per-launch token streaming
        # (readbacks ride the launch sync — no extra host syncs), a
        # completion feed the front-end drains between launches, and
        # request-level cancellation applied at step boundaries.
        self.stream_tokens = False
        self.stream_events: List[Tuple[int, int, np.ndarray]] = []
        self._slot_streamed = np.zeros(self.B, np.int64)
        self._newly_done: List[int] = []
        self._cancels: set = set()
        self.cancelled_requests = 0
        # evidence rows staged for the next launch (set by _begin)
        self._evid = None

    # ------------------------------------------------------------------
    # mesh placement
    # ------------------------------------------------------------------
    def _install_mesh(self, mesh):
        """Place params and engine state on the serving mesh: the decode
        batch and every per-slot state leaf shard over the data axis,
        paged KV pools over the page axis (boundaries matching the host
        allocator's per-shard page-id ranges), params replicated — or
        tensor-parallel via the training sharding rules when the mesh
        has a real "model" axis."""
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import (batch_leading_spec,
                                                cache_specs,
                                                engine_state_specs,
                                                serve_param_specs,
                                                to_shardings)
        specs = engine_state_specs(self.cfg, self.state, mesh)
        self._state_sharding = to_shardings(mesh, specs)
        self.state = jax.device_put(self.state, self._state_sharding)
        if self._arena_buf is not None:
            # arena rows partition over the data axis exactly like slot
            # rows: shard s's row range [s*rows_per_shard, ...) lands on
            # shard s, matching the host allocator's per-shard free lists
            self._arena_buf = jax.device_put(
                self._arena_buf,
                to_shardings(mesh, cache_specs(self.cfg, self._arena_buf,
                                               mesh)))
        self.params = jax.device_put(
            self.params,
            to_shardings(mesh, serve_param_specs(self.cfg, self.params,
                                                 mesh)))
        self._evid_sharding = NamedSharding(
            mesh, batch_leading_spec(mesh, (self.B, 1, self.d)))
        self._frontier_sharding = NamedSharding(
            mesh, batch_leading_spec(mesh, (self.B, 1)))

    def _reshard(self):
        """Pin the state back onto its canonical mesh placement before a
        decode launch. Host-side admission/bookkeeping scatters run
        eagerly and may leave leaves with drifted shardings; re-placing
        is a no-op for already-correct leaves and guarantees the jitted
        decode fns always see ONE input sharding (no per-pattern
        recompiles, and the macro-step loop stays device-resident)."""
        if self._state_sharding is not None:
            self.state = jax.device_put(self.state, self._state_sharding)

    def _slot_shard(self, s: int) -> int:
        """Data shard owning slot ``s`` (contiguous partition)."""
        return s // self.slots_per_shard

    def _quarantine(self, s: int) -> int:
        """Quarantine page idle slot ``s`` points its block table at —
        its own shard's reserved page, so dead writes stay local."""
        return self.pool.quarantine_page(self._slot_shard(s)) \
            if self.paged else 0

    @property
    def _reserved(self) -> int:
        """Total page reservations held by running candidates — derived
        from the per-shard ledger so the two can never drift."""
        return int(self._reserved_sh.sum())

    def _shard_headroom(self, s: int) -> int:
        """Pages shard ``s`` could fund right now: free + cache-evictable
        minus reservations already charged to it — THE admission-headroom
        definition, shared by seeding, placement, and affordability."""
        return self.pool.free_pages_in(s) + self.pool.evictable(s) \
            - int(self._reserved_sh[s])

    # ------------------------------------------------------------------
    def _sync(self, tree):
        """Decode-loop host readback: one counted synchronization."""
        self.host_syncs += 1
        return jax.device_get(tree)

    def _any_live(self) -> bool:
        """Host-side activity check — live slots mirror device ``active``
        exactly (slots are freed the moment their candidate finishes), so
        the per-iteration ``jnp.any(state.active)`` device round-trip of
        the old loop is free."""
        return bool((self._slot_req >= 0).any())

    # ------------------------------------------------------------------
    def _blank_state(self) -> EngineState:
        B, V, d = self.B, self.V, self.d
        if self.paged:
            cache = self.model.make_paged_cache(
                B, self.cache_len, self._dtype,
                page_size=self.page_size, num_pages=self.pool.num_pages,
                kv_dtype=self.kv_dtype)
            if self.dp > 1:
                # idle slots quarantine into their OWN shard's reserved
                # page (page 0 of each shard's id range) so dead writes
                # never cross shards
                q = np.asarray([[self._quarantine(s)] * self.pages_per_slot
                                for s in range(B)], np.int32)
                cache = {**cache, "block_table": jnp.asarray(q)}
        else:
            cache = self.model.make_cache(B, self.cache_len, self._dtype)
        return EngineState(
            cache=cache,
            last_token=jnp.zeros((B,), jnp.int32),
            token_counts=jnp.zeros((B, V), jnp.float32),
            sum_lp=jnp.zeros((B,), jnp.float32),
            n_tok=jnp.zeros((B,), jnp.int32),
            prev_h=jnp.zeros((B, d), jnp.float32),
            sum_coh=jnp.zeros((B,), jnp.float32),
            sum_emb=jnp.zeros((B, d), jnp.float32),
            align_sum=jnp.zeros((B,), jnp.float32),
            active=jnp.zeros((B,), bool),
            out_buf=jnp.zeros((B, self.max_new), jnp.int32),
            bias=jnp.zeros((B, V), jnp.float32),
            greedy=jnp.zeros((B,), bool),
            limit=jnp.full((B,), self.max_new, jnp.int32),
            hist=jnp.full((B, self.cache_len if self.spec else 1), -1,
                          jnp.int32),
            spec_k=jnp.ones((B,), jnp.int32),
        )

    # ------------------------------------------------------------------
    def _build_prefill(self):
        model = self.model

        @jax.jit
        def prefill_row(params, tokens, cache_row, evidence=None):
            lg, h, cache = model.prefill(params, tokens, cache_row,
                                         evidence, impl=self._model_impl)
            return lg, h, cache

        return prefill_row

    def _build_bucket_prefill(self):
        model, impl = self.model, self._model_impl

        @jax.jit
        def prefill_bucket(params, tokens, lengths, cache, evidence=None):
            return model.prefill(params, tokens, cache, evidence,
                                 impl=impl, lengths=lengths)

        return prefill_bucket

    def _build_first_tokens(self):
        sampling = self.sampling

        @jax.jit
        def first_tokens(keys, logits, bias, greedy):
            return sample_token_batch(keys, logits, sampling, bias=bias,
                                      greedy=greedy)

        return first_tokens

    def _build_suffix_prefill(self):
        """Continuation prefill for prefix-cache hits: only the prompt
        *suffix* runs, attending to the cached pages' K/V as context.
        Compiles once per (suffix_len, prefix_pages) shape pair."""
        model, impl = self.model, self._model_impl

        @jax.jit
        def prefill_suffix(params, tokens, cache_row, ctx, start):
            return model.prefill_suffix(params, tokens, cache_row, ctx,
                                        start, impl=impl)

        return prefill_suffix

    def _make_step_body(self):
        """One decode+sample+aggregate step — the body shared by the
        legacy jitted per-token step and the macro-step while_loop."""
        model, sampling, eos, max_new = self.model, self.sampling, \
            self.eos_id, self.max_new
        has_ev = self.has_evidence

        def decode_step(params, st: EngineState, key, evid_norm):
            logits, hidden, cache = model.decode_step(
                params, st.last_token, st.cache, impl=self._model_impl)
            tok, lp = sample_token(key, logits.astype(jnp.float32), sampling,
                                   st.token_counts, st.bias, greedy=st.greedy)
            act = st.active
            actf = act.astype(jnp.float32)
            hidden32 = hidden.astype(jnp.float32)

            # --- incremental CAMD aggregates ------------------------------
            sum_lp = st.sum_lp + lp * actf
            hn = hidden32 / (jnp.linalg.norm(hidden32, axis=-1, keepdims=True) + 1e-8)
            pn = st.prev_h
            coh = jnp.sum(hn * pn, axis=-1)
            has_prev = st.n_tok > 0
            sum_coh = st.sum_coh + coh * actf * has_prev.astype(jnp.float32)
            sum_emb = st.sum_emb + hidden32 * actf[:, None]
            if has_ev:
                emb_t = jnp.take(params["embed"]["table"], tok, axis=0)
                emb_t = emb_t.astype(jnp.float32)
                emb_t = emb_t / (jnp.linalg.norm(emb_t, axis=-1, keepdims=True) + 1e-8)
                a = jnp.mean(jnp.einsum("bnd,bd->bn", evid_norm, emb_t), axis=-1)
                align_sum = st.align_sum + a * actf
            else:
                align_sum = st.align_sum

            counts = st.token_counts + jax.nn.one_hot(tok, st.token_counts.shape[1]) \
                * actf[:, None]
            out_buf = jnp.where(
                (jnp.arange(max_new)[None, :] == st.n_tok[:, None]) & act[:, None],
                tok[:, None], st.out_buf)
            n_tok = st.n_tok + act.astype(jnp.int32)
            # per-slot limit (== max_new unless the scheduler granted a
            # tighter budget-constrained one) ends the candidate exactly
            # where the budget accounting assumed it would.
            done = act & ((tok == eos) | (n_tok >= st.limit))
            new_state = EngineState(
                cache=cache, last_token=jnp.where(act, tok, st.last_token),
                token_counts=counts, sum_lp=sum_lp, n_tok=n_tok,
                prev_h=jnp.where(act[:, None], hn, st.prev_h),
                sum_coh=sum_coh, sum_emb=sum_emb, align_sum=align_sum,
                active=act & ~done, out_buf=out_buf, bias=st.bias,
                greedy=st.greedy, limit=st.limit, hist=st.hist,
                spec_k=st.spec_k)
            return new_state, done

        return decode_step

    def _build_macro_step(self):
        """Fused decode loop: up to K steps of ``_step_body`` inside
        ``lax.while_loop``, exiting early when every slot goes inactive or
        any slot finishes (the host must fold the candidate / round).

        The paged block-table advance is inverted relative to the legacy
        host loop: instead of the host scattering a freshly-allocated page
        before every step, the device pulls the next page from the
        pre-staged ``frontier`` row whenever a slot's write position
        crosses a page boundary.
        """
        K = max(self.macro_steps, 1)
        paged = self.paged
        ps = self.page_size if paged else 0
        step_body = self._step_body
        B = self.B

        @partial(jax.jit, donate_argnums=(1,))
        def decode_launch(params, st: EngineState, base_key, t0, evid_norm,
                          frontier):
            F = frontier.shape[1]

            def cond(carry):
                st, fidx, done, i = carry
                return (i < K) & jnp.any(st.active) & ~jnp.any(done)

            def body(carry):
                st, fidx, done, i = carry
                if paged:
                    pos = st.cache["pos"]
                    bt = st.cache["block_table"]
                    need = st.active & (jnp.mod(pos, ps) == 0)
                    li = jnp.clip(pos // ps, 0, bt.shape[1] - 1)
                    page = jnp.take_along_axis(
                        frontier, jnp.clip(fidx, 0, F - 1)[:, None],
                        axis=1)[:, 0]
                    hit = jnp.arange(bt.shape[1])[None, :] == li[:, None]
                    bt = jnp.where(need[:, None] & hit, page[:, None], bt)
                    st = st._replace(cache={**st.cache, "block_table": bt})
                    fidx = fidx + need.astype(jnp.int32)
                key = decode_step_key(base_key, t0 + i)
                st, done = step_body(params, st, key, evid_norm)
                return st, fidx, done, i + jnp.int32(1)

            carry = (st, jnp.zeros((B,), jnp.int32),
                     jnp.zeros((B,), bool), jnp.int32(0))
            st, fidx, done, i = jax.lax.while_loop(cond, body, carry)
            return st, done, i

        return decode_launch

    def _coverage_k(self, p_star) -> int:
        """Per-candidate speculative verify width (1..spec_k).

        ``coverage`` mode shrinks the draft length toward 1 as the
        request's posterior coverage deficit closes — verify-compute
        follows the residual risk, mirroring the CAMD stopping rule.
        ``p_star`` is the request's current posterior coverage (None
        before the first round's rescore, which grants the full budget).
        """
        if not self.spec:
            return 1
        if self.spec_mode != "coverage":
            return self.spec_k
        deficit = max(0.0, (1.0 - self.camd.delta) - (p_star or 0.0))
        frac = min(1.0, deficit / max(1e-9, 1.0 - self.camd.delta))
        return 1 + int(round((self.spec_k - 1) * frac))

    def _ngram_draft(self, hist, pos, last):
        """Device-side n-gram draft proposal, vectorized over slots.

        ``hist[b, p]`` is the token fed at cache position p (prompt +
        committed decode tokens; -1 for evidence/unfed). The proposer
        finds an earlier position j whose context-gram ending at
        ``hist[j]`` matches the current suffix ending at the pending
        token ``last`` — deepest context first (``spec_ngram``-gram),
        backing off one token at a time to a plain 1-gram match — and
        proposes the spec_k-1 tokens that followed it. Within a context
        depth it prefers the most recent match with all spec_k-1
        followers known over a fresher partial match. Returns
        (B, spec_k-1) int32, -1 where no match / out of range — an
        unmatched draft position is simply never accepted, so a bad
        proposal costs nothing but wasted verify width."""
        B, H = hist.shape
        n_draft = self.spec_k - 1
        idx = jnp.arange(H)
        # j < pos-1: a match at the latest fed position has no known
        # followers (nothing to propose), and taking the max would shadow
        # an older match that does
        m = (hist == last[:, None]) & (idx[None, :] < pos[:, None] - 1)
        full = idx[None, :] + n_draft < pos[:, None]

        def pick(m):
            # most recent full-width match, else most recent partial
            # (periodic generations put the nearest match right at the
            # tail, where it can only seed a 1-token draft; an older
            # full match proposes the same continuation at full width)
            j_full = jnp.max(jnp.where(m & full, idx[None, :], -1), axis=1)
            j_any = jnp.max(jnp.where(m, idx[None, :], -1), axis=1)
            return jnp.where(j_full >= 0, j_full, j_any)

        j = pick(m)                                   # 1-gram fallback
        for g in range(1, self.spec_ngram):
            # context token g steps back from the pending token
            ctx = jnp.take_along_axis(
                hist, jnp.clip(pos[:, None] - g, 0, H - 1), axis=1)[:, 0]
            prev = jnp.pad(hist, ((0, 0), (g, 0)),
                           constant_values=-2)[:, :H]     # hist[j-g]
            m &= (idx[None, :] >= g) & (pos[:, None] >= g) & \
                (prev == ctx[:, None]) & (ctx[:, None] >= 0)
            jg = pick(m)
            j = jnp.where(jg >= 0, jg, j)             # deeper match wins
        src = j[:, None] + jnp.arange(1, n_draft + 1)[None, :]   # (B, n-1)
        ok = (j >= 0)[:, None] & (src < pos[:, None])
        d = jnp.take_along_axis(hist, jnp.clip(src, 0, H - 1), axis=1)
        return jnp.where(ok, d, -1)

    def _build_macro_step_spec(self):
        """Speculative macro-step loop: each iteration drafts up to
        spec_k-1 tokens per slot from the n-gram table, verifies the
        whole block with ONE batched target forward
        (``model.decode_block``), and commits the accepted prefix via
        ``samplers.speculative_accept`` — greedy rows byte-identical to
        the sequential loop, sampled rows distribution-preserving.

        The paged block-table advance is a pure function of the slot's
        position: logical page li maps to ``frontier[s, li - li0]`` with
        li0 fixed at launch start, so partial acceptance (pos advancing
        less than the mapped extent) is self-correcting — the next
        iteration simply re-maps the same frontier entries.
        """
        K = max(self.macro_steps, 1)
        Kb = self.spec_k
        paged = self.paged
        ps = self.page_size if paged else 0
        model, sampling, eos, max_new = self.model, self.sampling, \
            self.eos_id, self.max_new
        has_ev = self.has_evidence
        impl = self._model_impl
        B, V = self.B, self.V
        # every admitted row is greedy iff the engine mode is — a static
        # fact, so the accept kernel can take its vectorized greedy path
        all_greedy = self.mode == "greedy"

        @partial(jax.jit, donate_argnums=(1,))
        def decode_launch(params, st: EngineState, base_key, t0, evid_norm,
                          frontier):
            F = frontier.shape[1]
            # first logical page the frontier row maps to (fixed at
            # launch start — frontier entries are indexed by logical
            # page offset relative to this)
            li0 = -(-st.cache["pos"] // ps) if paged else None

            def cond(carry):
                st, done, i, nd, na = carry
                return (i < K) & jnp.any(st.active) & ~jnp.any(done)

            def body(carry):
                st, done, i, n_drafted, n_accepted = carry
                pos = st.cache["pos"]
                if paged:
                    bt = st.cache["block_table"]
                    nlog = bt.shape[1]
                    li = jnp.arange(nlog)[None, :]
                    fr_idx = li - li0[:, None]                 # (B, nlog)
                    need = st.active[:, None] & \
                        (li >= (pos // ps)[:, None]) & \
                        (li <= ((pos + Kb - 1) // ps)[:, None]) & \
                        (fr_idx >= 0) & (fr_idx < F)
                    page = jnp.take_along_axis(
                        frontier, jnp.clip(fr_idx, 0, F - 1), axis=1)
                    bt = jnp.where(need, page, bt)
                    st = st._replace(cache={**st.cache, "block_table": bt})

                draft = self._ngram_draft(st.hist, pos, st.last_token)
                # coverage-aware per-slot draft length: mask positions
                # beyond the slot's spec_k
                draft = jnp.where(
                    jnp.arange(Kb - 1)[None, :] < (st.spec_k - 1)[:, None],
                    draft, -1)
                blk = jnp.concatenate(
                    [st.last_token[:, None], jnp.maximum(draft, 0)], axis=1)
                # feedable positions: at most limit - n_tok more tokens
                # may be emitted, so later block positions never need KV
                valid = st.active[:, None] & \
                    (jnp.arange(Kb)[None, :] < (st.limit - st.n_tok)[:, None])
                logits, hidden, cache = model.decode_block(
                    params, blk, st.cache, valid, impl=impl)
                toks, lps, emit, counts, n_new, stopped = speculative_accept(
                    base_key, t0 + i * Kb, logits.astype(jnp.float32),
                    draft, sampling, token_counts=st.token_counts,
                    bias=st.bias, greedy=st.greedy, eos_id=eos,
                    n_tok=st.n_tok, limit=st.limit, active=st.active,
                    greedy_static=all_greedy)
                act = st.active
                emitf = emit.astype(jnp.float32)           # (B, Kb)
                n_emit = jnp.sum(emit, axis=1).astype(jnp.int32)
                last_i = jnp.maximum(n_emit - 1, 0)[:, None]

                # --- incremental CAMD aggregates over the block -------
                sum_lp = st.sum_lp + jnp.sum(lps * emitf, axis=1)
                hidden32 = hidden.astype(jnp.float32)      # (B, Kb, d)
                hn = hidden32 / (jnp.linalg.norm(
                    hidden32, axis=-1, keepdims=True) + 1e-8)
                prev_chain = jnp.concatenate(
                    [st.prev_h[:, None], hn[:, :-1]], axis=1)
                coh = jnp.sum(hn * prev_chain, axis=-1)    # (B, Kb)
                coh_w = emitf.at[:, 0].mul(
                    (st.n_tok > 0).astype(jnp.float32))
                sum_coh = st.sum_coh + jnp.sum(coh * coh_w, axis=1)
                sum_emb = st.sum_emb + jnp.sum(
                    hidden32 * emitf[:, :, None], axis=1)
                if has_ev:
                    emb_t = jnp.take(params["embed"]["table"], toks,
                                     axis=0).astype(jnp.float32)
                    emb_t = emb_t / (jnp.linalg.norm(
                        emb_t, axis=-1, keepdims=True) + 1e-8)
                    a = jnp.mean(jnp.einsum("bnd,bkd->bkn", evid_norm,
                                            emb_t), axis=-1)
                    align_sum = st.align_sum + jnp.sum(a * emitf, axis=1)
                else:
                    align_sum = st.align_sum

                # emitted tokens land at out_buf[n_tok .. n_tok+n_emit)
                tgt = st.n_tok[:, None] + jnp.arange(Kb)[None, :]
                out_buf = st.out_buf.at[
                    jnp.arange(B)[:, None],
                    jnp.where(emit, tgt, max_new)].set(toks, mode="drop")
                # fed tokens [last, toks[:-1]] enter the n-gram table at
                # positions pos .. pos+n_emit
                fed = jnp.concatenate(
                    [st.last_token[:, None], toks[:, :-1]], axis=1)
                hpos = pos[:, None] + jnp.arange(Kb)[None, :]
                hist = st.hist.at[
                    jnp.arange(B)[:, None],
                    jnp.where(emit, hpos, st.hist.shape[1])].set(
                        fed, mode="drop")

                last_tok = jnp.take_along_axis(toks, last_i, axis=1)[:, 0]
                prev_h = jnp.take_along_axis(
                    hn, last_i[:, :, None], axis=1)[:, 0]
                new_done = act & stopped
                cache = {**cache, "pos": pos + n_emit * act}
                st = EngineState(
                    cache=cache,
                    last_token=jnp.where(act, last_tok, st.last_token),
                    token_counts=counts, sum_lp=sum_lp, n_tok=n_new,
                    prev_h=jnp.where(act[:, None], prev_h, st.prev_h),
                    sum_coh=sum_coh, sum_emb=sum_emb, align_sum=align_sum,
                    active=act & ~new_done, out_buf=out_buf, bias=st.bias,
                    greedy=st.greedy, limit=st.limit, hist=hist,
                    spec_k=st.spec_k)
                n_drafted = n_drafted + jnp.sum(
                    (draft >= 0) & act[:, None]).astype(jnp.int32)
                n_accepted = n_accepted + jnp.sum(
                    jnp.maximum(n_emit - 1, 0) * act).astype(jnp.int32)
                return st, new_done, i + jnp.int32(1), n_drafted, n_accepted

            carry = (st, jnp.zeros((B,), bool), jnp.int32(0),
                     jnp.int32(0), jnp.int32(0))
            st, done, i, nd, na = jax.lax.while_loop(cond, body, carry)
            return st, done, i, nd, na

        return decode_launch

    # ------------------------------------------------------------------
    # host-side scheduling
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        # uids key the request table and results; a reused uid would
        # resurrect a finished request's bookkeeping (cache_row=None).
        if req.uid in self._reqs or any(r.uid == req.uid
                                        for r in self._queue):
            raise ValueError(f"duplicate request uid {req.uid}")
        if req.image is not None and req.evidence is None:
            self._encode_image(req)
        self._arrival[req.uid] = self._submit_seq
        self._submit_seq += 1
        self._t_submit[req.uid] = time.perf_counter_ns()
        self._queue.append(req)

    # -- image frontend ------------------------------------------------
    def _encode_image(self, req: Request) -> None:
        """Vision-tower encode at submit time: the image becomes the
        request's evidence embeddings — downstream prefill/scoring is
        unchanged. Features are memoized by content hash, so a repeated
        image (the multi-turn / shared-asset pattern) costs one dict
        lookup, and the same hash keys the cross-request prefix cache
        (``_prefix_token_stream``) so repeated images skip their pages'
        prefill entirely."""
        if self.cfg.vision is None:
            raise ValueError(
                f"request {req.uid} carries an image but {self.cfg.name} "
                "has no vision tower (cfg.vision is None)")
        img = np.ascontiguousarray(np.asarray(req.image, np.float32))
        digest = _hashlib.sha256(img.tobytes()).digest()
        self._image_digest[req.uid] = digest
        feats = self._image_feats.get(digest)
        if feats is None:
            if self._vision_fn is None:
                self._vision_fn = jax.jit(self.model.encode_image)
            feats = np.asarray(self._vision_fn(self.params, img[None])[0],
                               np.float32)
            self.image_encodes += 1
            self._image_feats[digest] = feats
            while len(self._image_feats) > 64:   # bounded FIFO memo
                self._image_feats.pop(next(iter(self._image_feats)))
        else:
            self.image_feat_hits += 1
        req.evidence = feats

    def _prefix_token_stream(self, req: Request) -> Optional[np.ndarray]:
        """The request's cache-position key stream for the prefix cache:
        one int64 per cache position. Text-only prompts are the prompt
        itself. Image requests prepend ``ne`` pseudo-tokens derived from
        the image content hash — two requests sharing image bytes and a
        prompt prefix then share page keys, so the image's KV pages hit
        across requests. Raw precomputed-evidence requests have no
        stable content key and stay uncacheable (None)."""
        if req.evidence is None:
            return np.asarray(req.prompt, np.int64)
        digest = self._image_digest.get(req.uid)
        if digest is None:
            return None
        ne = self.cfg.num_evidence_tokens
        rep = (digest * (ne * 8 // len(digest) + 1))[:ne * 8]
        pseudo = np.frombuffer(rep, np.int64).copy()
        return np.concatenate(
            [pseudo, np.asarray(req.prompt, np.int64)])

    def _cache_batch_axis(self, path) -> int:
        for p in path:
            if isinstance(p, jax.tree_util.DictKey) and p.key in (
                    "super", "self", "cross_k", "cross_v"):
                return 1
        return 0

    @staticmethod
    def _scat_rows(big, row, idx, ax: int):
        """Scatter a 1-row cache leaf into ``idx`` slots on batch axis
        ``ax`` (0 = per-slot leaves, 1 = layer-stacked leaves)."""
        r_rep = jnp.repeat(row, idx.shape[0], axis=ax)
        if ax == 0:
            return big.at[idx].set(r_rep)
        return big.at[:, idx].set(r_rep)

    def _scatter_cache_rows(self, big, row, slot_ids: List[int]):
        idx = jnp.asarray(slot_ids)
        return jax.tree_util.tree_map_with_path(
            lambda path, b, r: self._scat_rows(
                b, r, idx, self._cache_batch_axis(path)), big, row)

    def _slice_cache_row(self, cache, i: int):
        """A 1-row view of a batched prefill cache (row ``i``), matching
        the shapes ``_scatter_cache_rows`` / ``_write_pages`` expect."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf[:, i:i + 1]
            if self._cache_batch_axis(path) == 1 else leaf[i:i + 1], cache)

    # -- fixed-stride state arena (recurrent / hybrid slots) -----------
    def _arena_put(self, info) -> None:
        """Move a freshly prefilled prompt row into the state arena: one
        refcounted row hold (released at ``_finish_request``), so
        prefilled-but-unadmitted recurrent state is bounded and
        accounted instead of pinning anonymous per-request device
        buffers the way the dense kv path does."""
        if self.arena is None or info.get("cache_row") is None:
            return
        r = self.arena.alloc(1, self.arena.best_shard())[0]
        self._arena_buf = self._scatter_cache_rows(
            self._arena_buf, info["cache_row"], [r])
        info["cache_row"] = None
        info["arena_row"] = r

    def _request_row(self, info):
        """The request's 1-row prompt cache: an arena view for
        recurrent/hybrid engines, the per-request dense row otherwise."""
        r = info.get("arena_row")
        if r is not None:
            return self._slice_cache_row(self._arena_buf, r)
        return info["cache_row"]

    # -- paged cache plumbing ------------------------------------------
    def _page_shard_of(self, info, fallback: Optional[int] = None) -> int:
        """The shard a request's prompt pages live on (chosen once):
        prefix-cache holds pin it to the cached pages' shard; otherwise
        the caller's ``fallback`` (the first admitted slot's shard) or,
        at early-seed time, the least-loaded shard. Disaggregated
        engines (``prefill_shards`` set) ignore the fallback and place
        every prompt page on the least-loaded *prefill* shard — decode
        shards read those pages cross-shard, tail/frontier pages stay
        slot-local."""
        if "page_shard" not in info:
            held = info.get("prompt_pages")
            if held:
                info["page_shard"] = self.pool.shard_of(held[0])
            elif fallback is not None and not self.prefill_shards:
                info["page_shard"] = fallback
            else:
                info["page_shard"] = self._prefill_shard_pick()
        return info["page_shard"]

    def _prefill_shard_pick(self) -> int:
        """Least-loaded shard eligible to host prompt/chunk pages: the
        first ``prefill_shards`` shards when disaggregated, any shard
        otherwise."""
        k = self.prefill_shards or self.dp
        return int(np.argmax([self._shard_headroom(s) for s in range(k)]))

    def _seed_prompt_pages(self, info, shard: Optional[int] = None):
        """Allocate + write the request's full prompt pages (once per
        request — one pool hold each, released when the request
        finishes) and register them in the prefix cache. Prefix-cache
        hits arrive here already holding the cached prefix pages; only
        the remainder is written, from the suffix row (row positions =
        prompt positions - prefix_len). Under mesh sharding the pages
        come from ONE shard's subpool (``_page_shard_of``) — candidates
        on other shards reference them cross-shard, which GSPMD handles;
        tail/frontier pages stay shard-local."""
        if info.get("prompt_seeded"):
            return
        ps = self.page_size
        full = info["prompt_len"] // ps
        held = info.setdefault("prompt_pages", [])
        assert len(held) * ps == info.get("prefix_len", 0), \
            (len(held), info.get("prefix_len", 0))
        new_full = self.pool.alloc(full - len(held),
                                   self._page_shard_of(info, shard))
        if new_full:
            self.state = self.state._replace(cache=self._write_pages(
                self.state.cache, info["cache_row"], new_full, 0))
        info["prompt_pages"] = held + new_full
        if self.prefix_cache and info.get("cacheable"):
            self.pool.prefix.insert(info["page_keys"], info["prompt_pages"])
        info["prompt_seeded"] = True

    def _maybe_seed_early(self, req: Request):
        """Prefix-cache mode: seed + register prompt pages at *prefill*
        time (not first admission) so same-prefix requests later in the
        same batch already hit. Skipped when the pool lacks headroom —
        seeding then happens at admission, under admission control."""
        info = self._reqs[req.uid]
        if not info.get("cacheable") or info.get("prompt_seeded"):
            return
        L = info["prompt_len"]
        need = L // self.page_size - len(info.get("prompt_pages", ()))
        shard = self._page_shard_of(info)
        headroom = self._shard_headroom(shard)
        # keep at least one worst-case candidate fundable after seeding
        if headroom - need < self._pages_per_candidate(L):
            return
        self._seed_prompt_pages(info, shard)
        # early seeding must not eat into pages backing live reservations
        self._ensure_reserved_free()

    def _seed_paged_slots(self, info, slot_ids: List[int], lim: int):
        """Point ``slot_ids`` at the request's prompt pages.

        Full prompt pages are written to the pool once per request and
        *shared* (refcounted) across its candidates; the partially-filled
        tail page — the first page any candidate will write into, i.e.
        the CoW divergence point — is copied per candidate. Dense
        (non-paged: windowed attn / SSM / RG-LRU) entries scatter as in
        the contiguous path.

        With the cross-request prefix cache, ``info["prompt_pages"]`` may
        already hold the cached page-aligned prefix (request hold taken
        at prefill time); only the remaining full pages are allocated and
        written here, from the *suffix* prefill row (row positions are
        prompt positions minus ``info["prefix_len"]``). Newly written
        full pages are registered in the cache for future requests."""
        row = info["cache_row"]
        L = info["prompt_len"]                   # prompt incl. evidence
        ps = self.page_size
        assert L + lim <= self.cache_len, \
            f"prompt {L} + limit {lim} overflows paged cache " \
            f"of {self.cache_len} (paged KV does not ring-wrap)"
        full, tail_len = divmod(L, ps)
        row_off = info.get("prefix_len", 0)      # cache row starts here
        self._seed_prompt_pages(info, self._slot_shard(slot_ids[0]))
        cache = self.state.cache
        bt_rows = np.zeros((len(slot_ids), self.pages_per_slot), np.int32)
        tails = []
        for j, s in enumerate(slot_ids):
            sh = self._slot_shard(s)
            pages = list(info["prompt_pages"])
            self.pool.share(pages)
            if tail_len:
                # CoW tail + all future decode pages come from the
                # slot's own shard — the shard-locality invariant the
                # page-axis sharding leans on
                tail = self.pool.alloc(1, sh)
                tails += tail
                pages += tail
            self._slot_pages[s] = pages
            self._slot_pos[s] = L
            self._slot_limit[s] = L + lim
            future = self._pages_per_candidate(L, lim) - (1 if tail_len else 0)
            self._slot_reserved[s] = future
            self._reserved_sh[sh] += future
            bt_rows[j, :len(pages)] = pages
        if tails:
            # every candidate's tail page holds the same prompt bytes:
            # one broadcast scatter, not one full-pool copy per candidate
            cache = self._write_pages(cache, row, tails, full * ps - row_off,
                                      broadcast=True)
        if self.prefix_cache:
            # admission counted cache-evictable pages as headroom; convert
            # that headroom into ACTUALLY free pages now, before a later
            # prefix hit can re-pin them — reservations must always be
            # backed by the free list or frontier staging could fail
            # mid-decode
            self._ensure_reserved_free()
        idx = jnp.asarray(slot_ids)
        cache = {**cache,
                 "block_table": cache["block_table"].at[idx].set(
                     jnp.asarray(bt_rows)),
                 "pos": cache["pos"].at[idx].set(jnp.int32(L))}
        return self._scatter_dense_entries(cache, row, slot_ids)

    def _pages_per_candidate(self, prompt_len: int,
                             lim: Optional[int] = None) -> int:
        """Pages a candidate may allocate beyond the shared prompt pages:
        its private tail copy plus every boundary crossed while decoding
        up to ``lim`` (default ``max_new``) tokens."""
        ps = self.page_size
        lim = self.max_new if lim is None else lim
        total = -((prompt_len + lim) // -ps)                 # ceil
        return total - prompt_len // ps

    def _ensure_reserved_free(self):
        """Back every live reservation with ACTUALLY free pages of its
        own shard (evicting cached-only prefix pages if needed)."""
        if self.dp == 1:
            self.pool.ensure_free(self._reserved)
        else:
            for s in range(self.dp):
                self.pool.ensure_free(int(self._reserved_sh[s]), s)

    def _paged_affordable(self, info, want: int,
                          lim: Optional[int] = None) -> int:
        """How many candidates of this request fit in the pool right now
        (free + cache-evictable pages minus reservations held by running
        candidates and the request's unseeded prompt-page hold).

        Mesh-sharded pools make this shard-local: admission fills free
        slots in ascending order, so walk exactly those slots and fund
        each candidate's worst-case pages (CoW tail + decode frontier)
        from its slot's OWN shard; the shared prompt-page hold charges
        the request's page shard (the first admitted slot's, unless a
        prefix-cache hold already pinned one)."""
        L = info["prompt_len"]
        per_cand = self._pages_per_candidate(L, lim)
        need_hold = 0 if info.get("prompt_seeded") else \
            L // self.page_size - len(info.get("prompt_pages", ()))
        if self.dp == 1:
            avail = self.pool.free_pages + self.pool.evictable() \
                - self._reserved - need_hold
            return max(0, min(want, avail // max(per_cand, 1)))
        free = self._free_slots()[:want]
        if not free:
            return 0
        avail = [self._shard_headroom(s) for s in range(self.dp)]
        held = info.get("prompt_pages")
        if "page_shard" in info:
            hold_shard = info["page_shard"]
        elif held:
            hold_shard = self.pool.shard_of(held[0])
        elif self.prefill_shards:
            hold_shard = self._prefill_shard_pick()
        else:
            hold_shard = self._slot_shard(free[0])
        avail[hold_shard] -= need_hold
        if avail[hold_shard] < 0:
            # the shard pinned to hold the shared prompt pages cannot
            # fund them — admitting would crash _seed_prompt_pages
            # mid-admission instead of surfacing as queueing delay
            return 0
        take = 0
        for slot in free:
            sh = self._slot_shard(slot)
            if avail[sh] < per_cand:
                break
            avail[sh] -= per_cand
            take += 1
        return take

    def _write_pages(self, cache, row, pages: List[int], start: int,
                     broadcast: bool = False):
        """Copy prefill KV of the 1-row dense prefill cache into the given
        pool pages, every attention layer at once (stacked super entries +
        tail). Consecutive spans per page by default; ``broadcast=True``
        writes the single page-sized span at ``start`` into ALL pages
        (identical CoW tail copies for a round's candidates)."""
        if not pages:
            return cache
        n, ps = len(pages), self.page_size
        span = ps if broadcast else n * ps
        pg = jnp.asarray(pages)

        def seed(pool, spool, rk):
            """Scatter the row's span into value pages; quantized pools
            (``spool`` is the scale pool) quantize the span once and
            scatter values + scales — broadcasting after quantization
            keeps CoW copies bit-identical for free."""
            stacked = pool.ndim == 5  # (n_super, P, ps, Hkv, hd)
            if stacked:
                seg = jax.lax.dynamic_slice_in_dim(rk[:, 0], start, span,
                                                   axis=1)
                seg = seg.reshape(pool.shape[0], -1, *pool.shape[2:])
            else:
                seg = jax.lax.dynamic_slice_in_dim(rk[0], start, span,
                                                   axis=0)
                seg = seg.reshape(-1, *pool.shape[1:])
            sseg = None
            if spool is not None:
                seg, sseg = attn_lib.kv_quantize(seg, pool.dtype)
            if broadcast:
                seg = jnp.broadcast_to(
                    seg, (pool.shape[0], n) + pool.shape[2:] if stacked
                    else (n,) + pool.shape[1:])
                if sseg is not None:
                    sseg = jnp.broadcast_to(
                        sseg, (spool.shape[0], n) + spool.shape[2:]
                        if stacked else (n,) + spool.shape[1:])
            if stacked:
                pool = pool.at[:, pg].set(seg.astype(pool.dtype))
                if sseg is not None:
                    spool = spool.at[:, pg].set(sseg)
            else:
                pool = pool.at[pg].set(seg.astype(pool.dtype))
                if sseg is not None:
                    spool = spool.at[pg].set(sseg)
            return pool, spool

        def seed_entries(entries, row_entries):
            out = []
            for ce, re_ in zip(entries, row_entries):
                if isinstance(ce, dict) and "k_pages" in ce:
                    kp, ks = seed(ce["k_pages"], ce.get("k_scale"),
                                  re_["k"])
                    vp, vs = seed(ce["v_pages"], ce.get("v_scale"),
                                  re_["v"])
                    ce = {"k_pages": kp, "v_pages": vp}
                    if ks is not None:
                        ce = {**ce, "k_scale": ks, "v_scale": vs}
                out.append(ce)
            return tuple(out)

        return {**cache,
                "super": seed_entries(cache["super"], row["super"]),
                "tail": seed_entries(cache["tail"], row["tail"])}

    def _scatter_dense_entries(self, cache, row, slot_ids: List[int]):
        """Scatter the non-paged cache entries (windowed attn rings, SSM
        and RG-LRU states) of the prefill row into the given slots.
        Axes follow ``_cache_batch_axis``: "super" leaves are
        layer-stacked (batch at 1), tail leaves are per-slot (batch 0)."""
        idx = jnp.asarray(slot_ids)

        def scatter_entries(entries, row_entries, ax):
            out = []
            for ce, re_ in zip(entries, row_entries):
                if not (isinstance(ce, dict) and "k_pages" in ce):
                    ce = jax.tree.map(
                        lambda b, r: self._scat_rows(b, r, idx, ax), ce, re_)
                out.append(ce)
            return tuple(out)

        return {**cache,
                "super": scatter_entries(cache["super"], row["super"], 1),
                "tail": scatter_entries(cache["tail"], row["tail"], 0)}

    # -- page frontiers (macro-step paged decode) ----------------------
    @staticmethod
    def _page_crossings(lo: int, hi: int, ps: int) -> int:
        """Number of page boundaries (multiples of ``ps``) a slot's write
        position crosses over the half-open span [lo, hi)."""
        return -(-hi // ps) - (-(-lo // ps))

    def _stage_frontier(self) -> Tuple[Dict[int, Tuple[int, List[int]]],
                                       jax.Array]:
        """Reserve each live slot's next pages for one macro-step launch.

        Staged pages come out of the slot's admission-time reservation, so
        staging can never fail nor starve queued work: free-minus-reserved
        is invariant. Returns ({slot: (start_pos, pages)}, (B, F) frontier
        array; idle rows point at the quarantine page 0)."""
        F = self._frontier_width
        fr = np.zeros((self.B, F), np.int32)
        staged: Dict[int, Tuple[int, List[int]]] = {}
        ps = self.page_size
        for s in range(self.B):
            if self._slot_req[s] < 0:
                continue
            p = int(self._slot_pos[s])
            # worst-case advance: K iterations × the slot's (coverage-
            # aware) speculative block length
            adv = max(self.macro_steps, 1) * \
                (int(self._slot_spec[s]) if self.spec else 1)
            hi = min(p + adv, int(self._slot_limit[s]))
            need = self._page_crossings(p, hi, ps)
            if need > 0:
                assert need <= self._slot_reserved[s], \
                    (s, need, self._slot_reserved[s])
                pages = self.pool.stage_frontier(need, self._slot_shard(s))
                self._slot_reserved[s] -= need
                self._reserved_sh[self._slot_shard(s)] -= need
                fr[s, :need] = pages
            else:
                pages = []
            staged[s] = (p, pages)
        return staged, jnp.asarray(fr)

    def _count_decode_pages(self):
        """Add this launch's first step to the decode-walk counters: each
        active slot's live pages (its length, the token the step writes
        included, over the page size) and the table's width."""
        lengths = self._slot_pos[self._slot_req >= 0] + 1
        self.decode_pages_live += int(np.sum(-(-lengths // self.page_size)))
        self.decode_pages_table += self.B * self.pages_per_slot

    def _reclaim_frontier(self, staged, pos_np):
        """After a macro-step: keep the consumed frontier prefix as slot
        pages (the device advanced the block table through them, in
        order), return the rest to the pool and to the slot's
        reservation."""
        for s, (p0, pages) in staged.items():
            p1 = int(pos_np[s])
            used = self._page_crossings(p0, p1, self.page_size)
            assert used <= len(pages), (s, p0, p1, used, len(pages))
            self._slot_pages[s] += pages[:used]
            unused = pages[used:]
            if unused:
                self.pool.return_frontier(unused)
                self._slot_reserved[s] += len(unused)
                self._reserved_sh[self._slot_shard(s)] += len(unused)
            self._slot_pos[s] = p1

    def _alloc_step_pages(self):
        """Legacy per-token loop only: before each decode step, hand a
        fresh page to every live slot whose next write crosses a page
        boundary, and mirror the allocation into the device block
        table."""
        rows, cols, vals = [], [], []
        for s in range(self.B):
            if self._slot_req[s] < 0:
                continue
            p = int(self._slot_pos[s])
            if p % self.page_size == 0:
                li = p // self.page_size
                if li >= self.pages_per_slot:
                    raise RuntimeError(
                        f"slot {s} ran past the paged cache "
                        f"({p} >= {self.cache_len})")
                page = self.pool.alloc(1, self._slot_shard(s))[0]
                self._slot_pages[s].append(page)
                if self._slot_reserved[s] > 0:
                    self._slot_reserved[s] -= 1
                    self._reserved_sh[self._slot_shard(s)] -= 1
                rows.append(s)
                cols.append(li)
                vals.append(page)
            self._slot_pos[s] += 1
        if rows:
            cache = self.state.cache
            bt = cache["block_table"].at[
                jnp.asarray(rows), jnp.asarray(cols)].set(
                    jnp.asarray(vals, jnp.int32))
            self.state = self.state._replace(
                cache={**cache, "block_table": bt})

    def _bytes_per_page(self) -> int:
        """True resident bytes per pool page across every attention
        layer: quantized values + their scale tensors (CoW-shared pages
        share both). Feeds both telemetry and the pool's byte budget."""

        def per_leaf(leaf):
            # every paged leaf — values and quantization scales alike —
            # carries a num_pages axis (position depends on stacking)
            return leaf.size // self.pool.num_pages * leaf.dtype.itemsize

        bpp = 0
        for entries in (self.state.cache["super"], self.state.cache["tail"]):
            for e in entries:
                if isinstance(e, dict) and "k_pages" in e:
                    bpp += sum(per_leaf(leaf) for leaf in e.values())
        return bpp

    def kv_stats(self) -> Dict[str, Any]:
        """Pool accounting incl. resident KV bytes vs. the dense
        worst case (slots × cache_len) the paged layout replaces."""
        assert self.paged
        stats = self.pool.stats()
        bpp = self._bytes_per_page()
        stats["kv_dtype"] = self.kv_dtype
        stats["bytes_per_page"] = bpp
        stats["resident_kv_bytes"] = stats["in_use"] * bpp
        stats["peak_kv_bytes"] = stats["max_in_use"] * bpp
        stats["dense_equiv_bytes"] = self.B * self.pages_per_slot * bpp
        stats["decode_pages_live"] = self.decode_pages_live
        stats["decode_pages_table"] = self.decode_pages_table
        if self.pool.prefix is not None:
            pc = self.pool.prefix
            stats["prefix_cache"] = {
                "probes": pc.probes,
                "hits": pc.hits,                    # pages reused
                "misses": pc.misses,                # probes short of full hit
                "hit_tokens": pc.hit_tokens,        # prefill tokens skipped
                "bytes_saved": pc.hits * bpp,       # KV bytes not re-written
                "cached_pages": pc.cached_pages,
                "insertions": pc.insertions,
                "evictions": pc.evictions,
            }
        return stats

    def sched_stats(self) -> Dict[str, Any]:
        """Traffic-policy telemetry: budget accounting, admissions,
        declined rounds, starvation, cancellations, the queue wait of
        first admissions, and the host loop's gaps between launches."""
        s = dict(self.scheduler.stats())
        s["starved"] = len(self.starved_uids)
        s["prefill_calls"] = self.prefill_calls
        s["prefill_tokens"] = self.prefill_tokens
        s["chunk_calls"] = self.chunk_calls
        s["chunk_tokens"] = self.chunk_tokens
        s["cancelled_requests"] = self.cancelled_requests
        s["image_encodes"] = self.image_encodes
        s["image_feat_hits"] = self.image_feat_hits
        s["queue_wait_ns"] = self.queue_wait_ns
        s["first_admissions"] = self.first_admissions
        s["launch_gap_ns"] = self.launch_gap_ns
        s["launch_gaps"] = self.launch_gaps
        return s

    def span_stats(self) -> Dict[str, Any]:
        """Host-loop telemetry: the launch-gap and queue-wait counters
        (also in ``sched_stats``), each ``serve.*`` span's count, total
        and max ns, and the most recent pump's ns with its direct
        children's (``last_pump``; None before the first pump)."""
        st = self.spans.stats()
        return {"launch_gap_ns": self.launch_gap_ns,
                "launch_gaps": self.launch_gaps,
                "queue_wait_ns": self.queue_wait_ns,
                "first_admissions": self.first_admissions,
                "spans": st["spans"],
                "last_pump": st["last"].get("serve.pump")}

    def arena_stats(self) -> Dict[str, Any]:
        """Fixed-stride state-arena telemetry (recurrent/hybrid
        engines); ``{}`` on kv engines, mirroring ``kv_stats`` for the
        paged pool."""
        if self.arena is None:
            return {}
        s: Dict[str, Any] = dict(self.arena.stats())
        s["state_kind"] = self.state_kind
        bpr = sum(leaf.size // self.arena.num_rows * leaf.dtype.itemsize
                  for leaf in jax.tree.leaves(self._arena_buf))
        s["bytes_per_row"] = int(bpr)
        s["resident_state_bytes"] = int(bpr) * self.arena.num_rows
        return s

    def reset_stats(self) -> None:
        """Zero telemetry for engine reuse across bench cells/scenarios
        — without this, ``sched_stats``/``kv_stats`` counters (prefix
        hits, host syncs, spec telemetry, frontier peaks) accumulate
        across runs and pollute later cells. Serving state — request
        table, budget ledgers (``spent``/``committed``), prefix-cache
        contents, the decode-key position ``_t`` — is untouched: this
        resets what the engine *reports*, never what it *decides*."""
        self.total_steps = 0
        self.total_tokens = 0
        self.macro_launches = 0
        self.host_syncs = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.chunk_calls = 0
        self.chunk_tokens = 0
        self.cancelled_requests = 0
        self.image_encodes = 0
        self.image_feat_hits = 0
        self.launch_gap_ns = 0
        self.launch_gaps = 0
        self.queue_wait_ns = 0
        self.first_admissions = 0
        self.decode_pages_live = 0
        self.decode_pages_table = 0
        self._sync_end = None
        self.spans.reset()
        self.starved_uids.clear()
        self.scheduler.reset_stats()
        if self.paged:
            self.pool.reset_stats()
        if self.arena is not None:
            self.arena.reset_stats()

    # -- async front-end hooks -----------------------------------------
    def has_work(self) -> bool:
        """Anything live, queued, or pending a round."""
        return self._any_live() or self._has_pending()

    def drain_stream_events(self) -> List[Tuple[int, int, np.ndarray]]:
        """Token deltas ``(uid, cand_uid, tokens)`` emitted since the
        last drain (requires ``stream_tokens = True``)."""
        ev, self.stream_events = self.stream_events, []
        return ev

    def pop_finished(self) -> List[int]:
        """Uids finalized since the last call (completion + cancel)."""
        done, self._newly_done = self._newly_done, []
        return done

    def result(self, uid: int) -> Result:
        """Public per-request result accessor (the async front-end's
        completion path; ``run`` returns the same objects in bulk)."""
        return self._result(uid)

    def _admit(self, req: Request, slot_ids: List[int],
               limit: Optional[int] = None):
        """Seed slots with the request's prompt cache and sample the first
        token of each candidate from the prefill logits — one batched
        ``sample_token_batch`` dispatch over the round's split keys, not a
        Python loop of single-row samples. ``limit`` is the scheduler's
        per-candidate token grant (``None`` = the engine-wide max)."""
        lim = self.max_new if limit is None else min(int(limit), self.max_new)
        assert lim >= 1
        t_submit = self._t_submit.pop(req.uid, None)
        if t_submit is not None:
            self.queue_wait_ns += time.perf_counter_ns() - t_submit
            self.first_admissions += 1
        info = self._reqs[req.uid]
        st = self.state
        if self.spec and not self.paged:
            # speculative block writes must not ring-wrap (a block write
            # past cache_len would alias a live earlier position)
            assert info["prompt_len"] + lim <= self.cache_len, \
                f"prompt {info['prompt_len']} + limit {lim} overflows " \
                f"cache {self.cache_len} (speculation does not ring-wrap)"
        if self.paged:
            cache = self._seed_paged_slots(info, slot_ids, lim)
        else:
            cache = self._scatter_cache_rows(st.cache,
                                             self._request_row(info),
                                             slot_ids)
        idx = jnp.asarray(slot_ids)
        n = len(slot_ids)

        self.key, *keys = jax.random.split(self.key, n + 1)
        lg = info["prefill_logits"]                      # (1, V) fp32
        bias = info.get("bias")
        toks, lps = self._first_fn(jnp.stack(keys), lg, bias,
                                   self._greedy_row)
        h0 = info["prefill_hidden"]                      # (1, d) fp32
        hn0 = h0 / (jnp.linalg.norm(h0, axis=-1, keepdims=True) + 1e-8)
        V, d = self.V, self.d

        if self.has_evidence:
            emb_t = jnp.take(self.params["embed"]["table"], toks,
                             axis=0).astype(jnp.float32)
            emb_n = emb_t / (jnp.linalg.norm(emb_t, axis=-1, keepdims=True) + 1e-8)
            ev = info["evid_row"]                        # (1, Ne, d) normalized
            a0 = jnp.mean(jnp.einsum("nd,bd->bn", ev[0], emb_n), axis=-1)
        else:
            a0 = jnp.zeros((n,), jnp.float32)

        if self.spec:
            # n-gram table: prompt tokens at their cache positions
            # (evidence rows stay -1 and never match); the first sampled
            # token is *pending* (it is fed by the first verify block)
            H = self.cache_len
            ne = info["prompt_len"] - len(req.prompt)
            hrow = np.full(H, -1, np.int32)
            hrow[ne:info["prompt_len"]] = np.asarray(req.prompt, np.int32)
            hist_rows = jnp.asarray(np.tile(hrow, (n, 1)))
            k_eff = self._coverage_k(info.get("p_star"))
        else:
            hist_rows = None
            k_eff = 1

        new = self.state._replace(
            cache=cache,
            last_token=st.last_token.at[idx].set(toks),
            token_counts=st.token_counts.at[idx].set(
                jax.nn.one_hot(toks, V, dtype=jnp.float32)),
            sum_lp=st.sum_lp.at[idx].set(lps),
            n_tok=st.n_tok.at[idx].set(1),
            prev_h=st.prev_h.at[idx].set(jnp.repeat(hn0, n, axis=0)),
            sum_coh=st.sum_coh.at[idx].set(0.0),
            sum_emb=st.sum_emb.at[idx].set(jnp.zeros((n, d))),
            align_sum=st.align_sum.at[idx].set(a0),
            active=st.active.at[idx].set(True),
            out_buf=st.out_buf.at[idx].set(
                jnp.zeros((n, self.max_new), jnp.int32).at[:, 0].set(toks)),
            bias=st.bias.at[idx].set(
                jnp.repeat(bias if bias is not None else jnp.zeros((1, V)), n, axis=0)),
            greedy=st.greedy.at[idx].set(self.mode == "greedy"),
            limit=st.limit.at[idx].set(lim),
            hist=st.hist.at[idx].set(hist_rows) if self.spec else st.hist,
            spec_k=st.spec_k.at[idx].set(k_eff) if self.spec else st.spec_k,
        )
        self.state = new
        for s in slot_ids:
            self._slot_req[s] = req.uid
            self._slot_cand[s] = self._next_cand
            self._slot_lim[s] = lim
            self._slot_spec[s] = k_eff
            self._slot_streamed[s] = 0
            info["cand_slots"].append((self._next_cand, s))
            self._next_cand += 1
        if self.dp > 1:
            self.scheduler.note_shard_admission(
                self._slot_shard(s) for s in slot_ids)

    # -- prefill -------------------------------------------------------
    def _prompt_span(self, req: Request) -> int:
        """Cache positions the prompt occupies, incl. prepended evidence
        (decoder-only; enc-dec evidence feeds the encoder instead)."""
        ne = self.cfg.num_evidence_tokens \
            if (req.evidence is not None and
                not self.cfg.is_encoder_decoder) else 0
        return len(req.prompt) + ne

    def _init_info(self, req: Request, cache_row, lg, h, prompt_len: int):
        info = {
            "req": req,
            "cache_row": cache_row,
            "prefill_logits": lg.astype(jnp.float32),
            "prefill_hidden": h.astype(jnp.float32),
            "prompt_len": prompt_len,
            "camd": ctrl.init_state(self.camd, self.d, self.V),
            "bias": None,
            "round": 0,
            "cand_slots": [],
            "records": {},
            "align_const": 0.0,
            "done": False,
        }
        if self.has_evidence and req.evidence is not None:
            evp = jnp.asarray(req.evidence, jnp.float32)
            if "evidence_proj" in self.params:
                from repro.models.layers import dense
                evp = dense(jax.tree.map(lambda x: x.astype(jnp.float32),
                                         self.params["evidence_proj"]), evp)
            evn = evp / (jnp.linalg.norm(evp, axis=-1, keepdims=True) + 1e-8)
            info["evid_row"] = evn[None]
            # Eq. 8 term 2: text-evidence ↔ visual-evidence consistency —
            # prompt token embeddings vs evidence features, constant per req.
            temb = jnp.take(self.params["embed"]["table"],
                            jnp.asarray(req.prompt, jnp.int32),
                            axis=0).astype(jnp.float32)
            temb = temb / (jnp.linalg.norm(temb, axis=-1, keepdims=True) + 1e-8)
            if self.xmodal_rescore:
                # prompt-token rows for the fused kernel's term-2 max
                # reduction (already normalized; kernel renorm is a no-op)
                info["text_row"] = temb[None]                # (1, L, d)
            sim = temb @ evn.T                               # (L, Ne)
            info["align_const"] = float(jnp.mean(jnp.max(sim, axis=-1)))
            # difficulty prior for the traffic scheduler: normalized
            # entropy of each prompt token's evidence attachment. A
            # peaked attachment (every token clearly grounded in one
            # evidence item) reads easy; a diffuse one marks grounding
            # ambiguity — the kind of instance CAMD's heavy tail is made
            # of. Costs one host float beside align_const, at prefill.
            ne_ev = int(evn.shape[0])
            if ne_ev > 1:
                p_att = jax.nn.softmax(sim, axis=-1)
                ent = -jnp.sum(p_att * jnp.log(p_att + 1e-9), axis=-1)
                info["evidence_entropy"] = \
                    float(jnp.mean(ent)) / float(np.log(ne_ev))
            else:
                info["evidence_entropy"] = 0.0
        else:
            info["evid_row"] = jnp.zeros((1, 1, self.d), jnp.float32)
        self._reqs[req.uid] = info
        self._arena_put(info)

    def _prefill_request(self, req: Request):
        """Unbucketed fallback: one prefill call per request (recompiles
        per distinct prompt length)."""
        with self.spans("serve.prefill", rows=1, length=len(req.prompt)):
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            cache_row = self.model.make_cache(1, self.cache_len, self._dtype)
            ev = None
            if req.evidence is not None:
                ev = jnp.asarray(req.evidence, self._dtype)[None]
            lg, h, cache_row = self._prefill_fn(self.params, prompt,
                                                cache_row, ev)
            self.prefill_calls += 1
            self.prefill_tokens += self._prompt_span(req)
            self._init_info(req, cache_row, lg, h, self._prompt_span(req))

    # -- cross-request prefix cache ------------------------------------
    def _mark_cacheable(self, req: Request):
        """Record the request's page-key chain so its prompt pages get
        registered in the prefix cache at seed time."""
        if not self.prefix_cache:
            return
        stream = self._prefix_token_stream(req)
        if stream is None:
            return
        info = self._reqs[req.uid]
        info["page_keys"] = prefix_page_keys(stream, self.page_size)
        info["cacheable"] = True

    def _try_prefill_suffix(self, req: Request) -> bool:
        """Prefix-cache fast path: if a page-aligned prefix of the key
        stream (image pseudo-tokens + prompt, or the prompt alone) is
        cached, take a request hold on those pages and prefill only the
        *suffix*, attending to the cached pages' KV as context — the
        shared pages' prefill is skipped entirely. The hit is capped at
        ``(L-1)//page_size`` pages so at least one prompt token remains
        to produce last-token logits. An image request's hit must cover
        the whole image span (positions below ``ne`` hold embeddings,
        not tokens — no suffix forward can resume inside it)."""
        if not self.prefix_cache:
            return False
        stream = self._prefix_token_stream(req)
        if stream is None:
            return False
        usable = (len(stream) - 1) // self.page_size
        if usable <= 0:
            return False
        keys = prefix_page_keys(stream, self.page_size)
        pages = self.pool.prefix.match_and_hold(keys[:usable])
        if not pages:
            return False
        start = len(pages) * self.page_size
        ne = len(stream) - len(req.prompt)
        if start < ne:
            self.pool.free(pages)        # partial image hit: re-prefill
            return False
        with self.spans("serve.prefill", rows=1, length=len(stream) - start):
            suffix = jnp.asarray(stream[start:], jnp.int32)[None, :]
            ctx = self._gather_prefix_ctx(pages)
            cache_row = self.model.make_cache(1, self.cache_len, self._dtype)
            lg, h, cache_row = self._suffix_fn(
                self.params, suffix, cache_row, ctx, jnp.int32(start))
            self.prefill_calls += 1
            self.prefill_tokens += len(stream) - start      # suffix only
            self._init_info(req, cache_row, lg, h, len(stream))
        info = self._reqs[req.uid]
        info["prompt_pages"] = pages         # request hold already taken
        info["prefix_len"] = start
        info["page_keys"] = keys
        info["cacheable"] = True
        return True

    def _gather_prefix_ctx(self, pages: List[int]):
        """Assemble per-layer context K/V from cached pool pages:
        (n_super, 1, h*ps, Hkv, hd) per stacked super entry (batch axis
        inserted), (1, h*ps, Hkv, hd) per tail entry."""
        idx = jnp.asarray(pages, jnp.int32)

        def gather(entries):
            out = []
            for e in entries:
                assert isinstance(e, dict) and "k_pages" in e, \
                    "prefix cache requires all-attention paged layers"
                kp, vp = e["k_pages"], e["v_pages"]
                ks, vs = e.get("k_scale"), e.get("v_scale")
                if kp.ndim == 5:            # stacked: (n_super, P, ps, ..)
                    k = kp[:, idx].reshape(kp.shape[0], 1, -1, *kp.shape[3:])
                    v = vp[:, idx].reshape(vp.shape[0], 1, -1, *vp.shape[3:])
                    if ks is not None:      # dequantize int8/fp8 pages
                        k = attn_lib.kv_dequantize(
                            k, ks[:, idx].reshape(ks.shape[0], 1, -1,
                                                  *ks.shape[3:]))
                        v = attn_lib.kv_dequantize(
                            v, vs[:, idx].reshape(vs.shape[0], 1, -1,
                                                  *vs.shape[3:]))
                else:
                    k = kp[idx].reshape(1, -1, *kp.shape[2:])
                    v = vp[idx].reshape(1, -1, *vp.shape[2:])
                    if ks is not None:
                        k = attn_lib.kv_dequantize(
                            k, ks[idx].reshape(1, -1, *ks.shape[2:]))
                        v = attn_lib.kv_dequantize(
                            v, vs[idx].reshape(1, -1, *vs.shape[2:]))
                out.append((k, v))
            return tuple(out)

        cache = self.state.cache
        return {"super": gather(cache["super"]),
                "tail": gather(cache["tail"])}

    # -- chunked prefill -----------------------------------------------
    def _start_chunk_job(self, req: Request) -> None:
        """Open a chunked-prefill job for a long prompt: probe the
        prefix cache for a page-aligned head (the hit pages are the
        job's first chunks, already resident), pick the page shard the
        whole prompt will live on, and register the cursor. If the
        cached head leaves at most one chunk of work, the one-shot
        suffix/whole paths are strictly better — no job is opened.
        Image requests chunk over their key stream (image pseudo-tokens
        + prompt): the first chunk carries the whole image span, and a
        cached head that ends inside the image span is unusable (those
        positions hold embeddings, not resumable tokens)."""
        stream = self._prefix_token_stream(req)
        assert stream is not None
        ne = len(stream) - len(req.prompt)
        pages: List[int] = []
        cur = 0
        if self.prefix_cache:
            usable = (len(stream) - 1) // self.page_size
            if usable > 0:
                keys = prefix_page_keys(stream, self.page_size)
                pages = self.pool.prefix.match_and_hold(keys[:usable]) or []
                cur = len(pages) * self.page_size
                if pages and cur < ne:
                    self.pool.free(pages)   # partial image hit
                    pages, cur = [], 0
        if len(stream) - cur <= self.chunk:
            if pages:
                self.pool.free(pages)    # release the probe hold
            return
        shard = self.pool.shard_of(pages[0]) if pages \
            else self._prefill_shard_pick()
        self._chunking[req.uid] = {"req": req, "pos": cur, "pages": pages,
                                   "shard": shard}

    def _run_chunk(self, uid: int, job: Dict[str, Any]) -> int:
        """Advance one job by one chunk; returns chunk tokens consumed
        (0 when the job's shard cannot fund the chunk's pages yet).

        Non-final chunks run the suffix forward against the job's pages
        as context and write their K/V into freshly allocated pool pages
        (page-aligned by construction). The FINAL chunk instead keeps
        its dense prefill row and promotes the job to a normal request
        record — ``info`` is indistinguishable from a prefix-cache
        suffix prefill (prompt_pages = chunk pages, prefix_len =
        cursor), so admission, seeding and teardown are unchanged."""
        req = job["req"]
        stream = self._prefix_token_stream(req)
        ne = len(stream) - len(req.prompt)
        L, cur, ps = len(stream), job["pos"], self.page_size
        final = L - cur <= self.chunk
        take = L - cur if final else self.chunk
        if not final:
            # keep one worst-case candidate fundable after this chunk —
            # chunk pages must never starve admission into deadlock
            need = take // ps
            if self._shard_headroom(job["shard"]) - need < \
                    self._pages_per_candidate(L):
                return 0
        cache_row = self.model.make_cache(1, self.cache_len, self._dtype)
        if cur == 0:
            # the first chunk carries the whole image span (pseudo-token
            # positions [0, ne) are evidence embeddings, not tokens):
            # feed the evidence through the normal prefill frontend and
            # only the chunk's real-token remainder as tokens
            ev = None
            if ne:
                assert take > ne, \
                    f"prefill_chunk {self.chunk} must exceed the image " \
                    f"span ({ne} evidence tokens)"
                ev = jnp.asarray(req.evidence, self._dtype)[None]
            toks = jnp.asarray(np.asarray(req.prompt)[:take - ne],
                               jnp.int32)[None, :]
            lg, h, cache_row = self._prefill_fn(self.params, toks,
                                                cache_row, ev)
        else:
            toks = jnp.asarray(stream[cur:cur + take], jnp.int32)[None, :]
            ctx = self._gather_prefix_ctx(job["pages"])
            lg, h, cache_row = self._suffix_fn(self.params, toks, cache_row,
                                               ctx, jnp.int32(cur))
        self.chunk_calls += 1
        self.chunk_tokens += take
        if not final:
            new_pages = self.pool.alloc(need, job["shard"])
            # the chunk row holds K/V for [cur, cur+take) at row
            # positions [0, take)
            self.state = self.state._replace(cache=self._write_pages(
                self.state.cache, cache_row, new_pages, 0))
            job["pages"] = job["pages"] + new_pages
            job["pos"] = cur + take
            return take
        del self._chunking[uid]
        self.prefill_calls += 1
        self.prefill_tokens += take
        self._init_info(req, cache_row, lg, h, L)
        info = self._reqs[uid]
        info["prompt_pages"] = job["pages"]     # request hold carried over
        info["prefix_len"] = cur
        info["page_shard"] = job["shard"]
        if self.prefix_cache:
            info["page_keys"] = prefix_page_keys(stream, ps)
            info["cacheable"] = True
            self._maybe_seed_early(req)
        return take

    def _prefill_chunks(self) -> None:
        """One chunked-prefill pass: open jobs for long prompts in the
        admission window, then spend the per-turn chunk-token budget on
        the policy-ranked jobs. When no slot is decoding there is
        nothing to protect — the budget is ignored, but the pass stops
        as soon as a job completes so the request admits immediately
        (cold-start TTFT)."""
        if not self.chunked:
            return
        ahead = max(self.B, 4)
        ne = self.cfg.num_evidence_tokens
        for r in self._queue[:ahead]:
            if r.uid in self._reqs or r.uid in self._chunking:
                continue
            stream = self._prefix_token_stream(r)
            if stream is None or len(stream) <= self.chunk:
                continue
            if len(stream) > len(r.prompt) and self.chunk <= ne:
                continue    # image span doesn't fit one chunk: one-shot
            self._start_chunk_job(r)
        if not self._chunking:
            return
        items = [PrefillWork(uid=uid, arrival=self._arrival[uid],
                             prompt_len=len(job["req"].prompt),
                             prefilled=job["pos"])
                 for uid, job in self._chunking.items()]
        idle = not self._any_live()
        for w in self.scheduler.prefill_order(items):
            while True:
                job = self._chunking.get(w.uid)
                if job is None:
                    if idle:
                        return       # a request just became admissible
                    break
                if not idle and self._chunk_left <= 0:
                    return
                with self.spans("serve.prefill", uid=w.uid, rows=1,
                                length=self.chunk):
                    took = self._run_chunk(w.uid, job)
                if took == 0:
                    break            # shard can't fund the chunk yet
                self._chunk_left -= took
                self._chunk_progress = True

    def _bucket_len(self, prompt_len: int) -> int:
        return _next_pow2(max(prompt_len, self.prefill_bucket_min))

    def _prefill_pending(self):
        """Prefill queued requests that have no cache yet, batching
        same-bucket prompts (right-padded to power-of-two lengths) into
        one prefill call each — instead of one recompile-per-length call
        per request. Only a bounded queue prefix is prefilled (admission
        is FIFO, so a prefix is always the next work): each prefilled
        request pins a dense cache row until admission, and an unbounded
        queue must not pin O(queue) rows of KV."""
        self._prefill_chunks()
        ahead = max(self.B, 4)
        pending = [r for r in self._queue[:ahead]
                   if r.uid not in self._reqs and
                   r.uid not in self._chunking]
        if self.arena is not None and len(pending) > self.arena.free_rows:
            # arena-bounded prefill-ahead: defer the overflow to the next
            # pass instead of letting prompt rows outgrow the arena
            self.arena.sizing_stalls += 1
            pending = pending[:self.arena.free_rows]
        if not pending:
            return
        # prefix-cache hits take the suffix path (skipping the shared
        # pages' prefill). Cacheable misses are prefilled one by one with
        # their pages seeded immediately, so same-prefix requests later
        # in the SAME batch hit too (the trade against bucketed batching
        # applies only when the prefix cache is on). Image requests are
        # cacheable through their content-hash pseudo-token stream.
        if self.prefix_cache:
            misses = []
            for r in pending:
                if self._try_prefill_suffix(r):
                    self._maybe_seed_early(r)
                elif self._prefix_token_stream(r) is not None:
                    self._prefill_request(r)
                    self._mark_cacheable(r)
                    self._maybe_seed_early(r)
                else:
                    misses.append(r)
            pending = misses
            if not pending:
                return
        if not self.bucket_prefill:
            for r in pending:
                self._prefill_request(r)
                self._mark_cacheable(r)
            return
        groups: Dict[Tuple[int, int], List[Request]] = {}
        for r in pending:
            ne = self.cfg.num_evidence_tokens if r.evidence is not None else 0
            groups.setdefault((self._bucket_len(len(r.prompt)), ne),
                              []).append(r)
        for (Lb, ne), reqs in sorted(groups.items()):
            if Lb + ne > min(self._min_ring, self.cache_len):
                # padded bucket would wrap an attention ring — the padded
                # tail analysis no longer holds, take the exact 1-row path
                for r in reqs:
                    self._prefill_request(r)
            else:
                with self.spans("serve.prefill", rows=len(reqs), length=Lb):
                    self._prefill_bucket(Lb, ne, reqs)
            for r in reqs:
                self._mark_cacheable(r)

    def _prefill_bucket(self, Lb: int, ne: int, reqs: List[Request]):
        n = len(reqs)
        nb = _next_pow2(n)          # row count buckets too: bounded recompiles
        toks = np.zeros((nb, Lb), np.int32)
        lens = np.full((nb,), Lb + ne, np.int32)   # dummy rows: full length
        for i, r in enumerate(reqs):
            toks[i, :len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt) + ne
        ev = None
        if ne:
            De = self.cfg.evidence_dim or self.d
            ev_np = np.zeros((nb, ne, De), np.float32)
            for i, r in enumerate(reqs):
                ev_np[i] = r.evidence
            ev = jnp.asarray(ev_np, self._dtype)
        cache = self.model.make_cache(nb, self.cache_len, self._dtype)
        lg, h, cache = self._bucket_fn(self.params, jnp.asarray(toks),
                                       jnp.asarray(lens), cache, ev)
        self.prefill_calls += 1
        self.prefill_tokens += int(sum(lens[:n]))
        for i, r in enumerate(reqs):
            self._init_info(r, self._slice_cache_row(cache, i),
                            lg[i:i + 1], h[i:i + 1], int(lens[i]))

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.B) if self._slot_req[i] < 0]

    def _per_round(self) -> int:
        if self.mode == "greedy":
            return 1
        if self.mode == "camd":
            return self.camd.samples_per_round
        return min(self.n_candidates, self.B)

    def _schedule(self):
        """Fill free slots — every admission/round decision is delegated
        to the traffic policy (``self.scheduler``) through the
        ``SchedulerContext`` facade.

        Paged backpressure: a request is only admitted when the pool can
        cover its candidates' worst-case pages (``_paged_affordable``);
        otherwise it waits in the queue / stays pending until running
        candidates finish and return pages."""
        with self.spans("serve.schedule"):
            self._prefill_pending()
            self.scheduler.schedule(_EngineSchedContext(self))

    def _needed(self, info) -> int:
        if self.mode == "camd":
            return self.camd.samples_per_round
        done_cands = len(info["records"])
        running = sum(1 for _, s in info["cand_slots"]
                      if self._slot_req[s] == info["req"].uid)
        return max(0, self.n_candidates - done_cands - running)

    # ------------------------------------------------------------------
    def _xmodal_fn(self, tokens: np.ndarray, evid_row, text_row):
        """S_align for one finished candidate via the fused Eq. 8-9
        kernel (``kernels.ops`` picks mosaic/interpret/ref per
        platform). Tokens pad to ``max_new`` so the call compiles once
        per prompt length, not per generation length."""
        if self._xmodal_jit is None:
            from repro.kernels import ops as kops

            def fn(params, toks, mask, evid, text):
                emb = jnp.take(params["embed"]["table"], toks,
                               axis=0).astype(jnp.float32)
                emb = emb / (jnp.linalg.norm(emb, axis=-1,
                                             keepdims=True) + 1e-8)
                return kops.xmodal_score(emb[None], mask[None], evid,
                                         text)[0]

            self._xmodal_jit = jax.jit(fn)
        n = len(tokens)
        toks = np.zeros(self.max_new, np.int32)
        toks[:n] = tokens
        mask = (np.arange(self.max_new) < n).astype(np.float32)
        return self._xmodal_jit(self.params, jnp.asarray(toks),
                                jnp.asarray(mask), evid_row, text_row)

    def _finish_candidates(self, slots: List[int]):
        """Fold finished slots into candidate records: ONE batched
        ``device_get`` of the finished rows (the legacy loop issued ~7
        scalar readbacks per slot), then host bookkeeping."""
        st = self.state
        idx = jnp.asarray(slots)
        out_buf, sum_lp, n_tok, sum_coh, sum_emb, align_sum, counts = \
            self._sync((st.out_buf[idx], st.sum_lp[idx], st.n_tok[idx],
                        st.sum_coh[idx], st.sum_emb[idx], st.align_sum[idx],
                        st.token_counts[idx]))
        uids: List[int] = []
        for j, slot in enumerate(slots):
            uid = int(self._slot_req[slot])
            cand = int(self._slot_cand[slot])
            info = self._reqs[uid]
            n = int(n_tok[j])
            rec = {
                "uid": cand,
                "tokens": np.asarray(out_buf[j])[:n],
                "sum_lp": float(sum_lp[j]),
                "n": n,
                "sum_coh": float(sum_coh[j]),
                "emb": np.asarray(sum_emb[j]) / max(n, 1),
                "align": float(align_sum[j]) / max(n, 1),
                "counts": np.asarray(counts[j]),
            }
            # Eq. 12 evidence-weighted score from incremental aggregates
            s_gen = rec["sum_lp"] / max(n, 1)
            s_coh = rec["sum_coh"] / max(n - 1, 1)
            s_align = 0.5 * (rec["align"] + info["align_const"]) \
                if self.has_evidence else 0.0
            if self.xmodal_rescore and "text_row" in info and n > 0:
                # recompute S_align through the fused Eq. 8-9 kernel
                # over the candidate's generated-token embeddings — the
                # block-reduced equivalent of the incremental aggregate
                # (same math, kernel-verified), recorded per candidate
                s_align = float(self._xmodal_fn(
                    rec["tokens"], info["evid_row"], info["text_row"]))
                rec["s_align_xmodal"] = s_align
            rec["score"] = s_gen + self.camd.lambda_g * s_align \
                + self.camd.lambda_c * s_coh
            info["records"][cand] = rec
            self._slot_req[slot] = -1
            self._slot_cand[slot] = -1
            self._slot_spec[slot] = 1
            self.total_tokens += n
            # release the candidate's worst-case token commitment; its
            # unspent remainder immediately funds queued work
            self.scheduler.on_finish(uid, n, int(self._slot_lim[slot]))
            self._slot_lim[slot] = self.max_new
            if self.paged:
                # return the candidate's pages (shared prompt pages just
                # drop a holder)
                self.pool.free(self._slot_pages[slot])
                self._slot_pages[slot] = []
                self._reserved_sh[self._slot_shard(slot)] -= \
                    int(self._slot_reserved[slot])
                self._slot_reserved[slot] = 0
            if uid not in uids:
                uids.append(uid)
        if self.paged:
            # quarantine the freed slots' block tables in one scatter so
            # their dead writes land on their shard's reserved page
            cache = self.state.cache
            quar = jnp.asarray([self._quarantine(s) for s in slots],
                               jnp.int32)
            bt = cache["block_table"].at[idx].set(quar[:, None])
            self.state = self.state._replace(
                cache={**cache, "block_table": bt})
        # rounds complete when no slots of the request remain live
        due = [u for u in uids
               if not any(self._slot_req[s] == u for s in range(self.B))]
        if due:
            self._finish_rounds(due)

    def _finish_rounds(self, uids: List[int]):
        """Fold completed rounds — ALL of them in one call to the vmapped
        ``batched_round_update_assign`` (a macro-step often retires several
        requests' rounds at once; the legacy loop dispatched one round
        update per request)."""
        R = self._per_round()
        batch = []
        for uid in uids:
            info = self._reqs[uid]
            round_recs = [info["records"][c] for c, _ in info["cand_slots"]
                          if c in info["records"] and
                          "scored" not in info["records"][c]]
            if not round_recs:
                continue
            for r in round_recs:
                r["scored"] = True
            assert len(round_recs) <= R, \
                (len(round_recs), R)   # scheduler admits ≤ per_round/round
            pad = R - len(round_recs)
            recs = round_recs + round_recs[:1] * pad
            inp = ctrl.RoundInputs(
                scores=np.asarray([r["score"] for r in recs], np.float32),
                embs=np.stack([r["emb"] for r in recs]).astype(np.float32),
                token_counts=np.stack([r["counts"] for r in recs]
                                      ).astype(np.float32),
                lengths=np.asarray([r["n"] for r in recs], np.int32),
                valid=np.asarray([True] * len(round_recs) + [False] * pad),
                uids=np.asarray([r["uid"] for r in recs], np.int32),
            )
            batch.append((uid, round_recs, inp))
        if not batch:
            return
        states = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[self._reqs[u]["camd"] for u, _, _ in batch])
        inps = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[b[2] for b in batch])
        if self.mode != "camd":
            # the coverage/max_rounds stop rule is CAMD's token-budget
            # policy; the fixed-budget baselines must keep folding every
            # round into the cluster table (a frozen table would orphan
            # late candidates from self-consistency's majority vote and
            # freeze best_of_n's best-candidate tracking).
            states = states._replace(stopped=jnp.zeros_like(states.stopped))
        # pad the batch to a power of two (repeat row 0, discard results)
        # so the vmapped round update compiles for O(log B) shapes, not
        # one per distinct simultaneous-completion count
        n = len(batch)
        nb = _next_pow2(n)
        if nb > n:
            states, inps = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[:1], nb - n, axis=0)]), (states, inps))
        new_states, biases, clusters = self._round_fn(states, inps)
        stopped_np, clusters_np, pstar_np, best_np = self._sync(
            (new_states.stopped, clusters, new_states.p_star,
             new_states.best_score))
        for i, (uid, round_recs, _) in enumerate(batch):
            info = self._reqs[uid]
            info["camd"] = jax.tree.map(lambda x, i=i: x[i], new_states)
            # host copies the traffic scheduler ranks by (folded into the
            # round sync above — no extra device round-trip)
            info["p_star"] = float(pstar_np[i])
            info["best_score_host"] = float(best_np[i])
            for j, r in enumerate(round_recs[:R]):
                r["cluster"] = int(clusters_np[i, j])
            info["round"] += 1
            if self.mode == "camd":
                info["bias"] = biases[i][None]
                stopped = bool(stopped_np[i])
            else:
                info["bias"] = None
                stopped = len(info["records"]) >= self.n_candidates
            if stopped:
                self._finish_request(uid)
            else:
                info["pending_round"] = True

    def _finish_request(self, uid: int):
        """Finalize a request with the candidates it has: free its prompt
        cache row and paged prompt-page holds. Used when the stop rule
        trips, when the coverage policy declines further rounds, and by
        the budget-exhaustion drain."""
        info = self._reqs[uid]
        info["done"] = True
        info["pending_round"] = False
        self._t_submit.pop(uid, None)     # never admitted (cancel, drain)
        info["cache_row"] = None          # free the prompt cache
        r = info.pop("arena_row", None)
        if r is not None:
            self.arena.free([r])
        if self.paged and info.get("prompt_pages"):
            self.pool.free(info.pop("prompt_pages"))
        # completion feed for the async front-end (drained via
        # pop_finished; harmless growth under synchronous run)
        self._newly_done.append(uid)

    # ------------------------------------------------------------------
    def _has_pending(self) -> bool:
        return bool(self._queue) or any(
            not i["done"] and i.get("pending_round")
            for i in self._reqs.values())

    def _raise_pool_sizing(self):
        # nothing running and nothing admissible: the pool cannot cover
        # even one candidate of the waiting work (FIFO head-of-line) — a
        # sizing error, not a transient.
        blocked = self._queue[0].uid if self._queue else \
            next(uid for uid, i in self._reqs.items() if not i["done"])
        done_n = sum(1 for i in self._reqs.values() if i["done"])
        raise RuntimeError(
            f"paged KV pool ({self.pool.num_pages} pages of "
            f"{self.page_size}) cannot admit request "
            f"{blocked} ({done_n} completed results "
            f"discarded) — raise num_pages or lower "
            f"max_new_tokens/prompt lengths")

    def _finalize_starved(self):
        """Terminal drain under an exhausted global token budget: pending
        work that can never be funded again finalizes with whatever
        candidates it already has (possibly none — ``Result.tokens``
        empty, recorded in ``starved_uids``). The budget invariant
        (total tokens <= budget) is preserved; nothing hangs."""
        for job in self._chunking.values():
            # half-prefilled chunk pages can never be used again
            if job["pages"]:
                self.pool.free(job["pages"])
        self._chunking.clear()
        for req in self._queue:
            if req.uid not in self._reqs:
                self._reqs[req.uid] = {
                    "req": req, "cache_row": None,
                    "camd": ctrl.init_state(self.camd, self.d, self.V),
                    "bias": None, "round": 0, "cand_slots": [],
                    "records": {}, "align_const": 0.0, "done": False}
        self._queue.clear()
        for uid, info in self._reqs.items():
            if not info["done"]:
                if not info["records"]:
                    self.starved_uids.append(uid)
                self._finish_request(uid)

    def _refill_idle(self) -> bool:
        """No slot is live: drain the queue / pending rounds back into
        slots. Returns True when all work is complete (caller breaks)."""
        if not self._has_pending():
            return True
        self._chunk_progress = False
        self._schedule()
        if not self._any_live():
            if self.scheduler.exhausted():
                # global token budget spent: nothing can ever be admitted
                # again — finalize instead of spinning
                self._finalize_starved()
                return True
            if self._chunk_progress:
                # chunked prefill advanced — not a sizing error, the
                # caller loops and the next pass continues the job
                return False
            if self.paged:
                self._raise_pool_sizing()
            if self.arena is not None:
                # defensively unreachable: a full arena means held rows,
                # and held rows mean live or admissible work — fail fast
                # instead of spinning if that invariant ever breaks
                raise RuntimeError(
                    f"state arena ({self.arena.num_rows} rows, "
                    f"{self.arena.free_rows} free) cannot admit pending "
                    "work — arena sizing invariant violated")
        return False

    def run(self) -> List[Result]:
        if self.macro_steps <= 0:
            return self._run_legacy()
        self._begin()
        while self._step():
            pass
        return [self._result(uid) for uid in self._reqs]

    def _begin(self):
        """Admission pass + evidence-row staging before stepping."""
        self._schedule()
        evid = jnp.zeros((self.B, 1, self.d), jnp.float32)
        if self._evid_sharding is not None:
            evid = jax.device_put(evid, self._evid_sharding)
        if self.has_evidence:
            evid = self._gather_evid()
        self._evid = evid

    def _step(self) -> bool:
        """One fused-loop serving iteration: refill when idle, otherwise
        stage the frontier, run one macro launch and fold its results
        (cancellations first, then token streaming, frontier reclaim,
        finished candidates). Returns False when all work is drained —
        this is the old ``run`` loop body verbatim, extracted so the
        async front-end can drive the engine launch-by-launch."""
        self._chunk_left = self.chunk_budget     # per-turn chunk budget
        if not self._any_live():
            if self._refill_idle():
                self._sync_end = None            # idle: no launch gap
                return False
            if self.has_evidence:
                self._evid = self._gather_evid()
            return True
        with self.spans("serve.stage"):
            staged, frontier = (self._stage_frontier() if self.paged
                                else (None, self._dummy_frontier))
            if self._frontier_sharding is not None:
                frontier = jax.device_put(frontier, self._frontier_sharding)
            self._reshard()
        with self.spans("serve.launch"):
            if self._sync_end is not None:
                self.launch_gap_ns += time.perf_counter_ns() - self._sync_end
                self.launch_gaps += 1
            if self.paged:
                self._count_decode_pages()
            if self.spec:
                self.state, done, steps, nd, na = self._macro_fn(
                    self.params, self.state, self._decode_key,
                    jnp.int32(self._t), self._evid, frontier)
            else:
                self.state, done, steps = self._macro_fn(
                    self.params, self.state, self._decode_key,
                    jnp.int32(self._t), self._evid, frontier)
            self.macro_launches += 1
        # ONE host sync per launch: cancellation emission counts and
        # streaming readbacks ride the tuple the fold already needs
        tree = [done, self.state.cache["pos"], steps]
        if self.spec:
            tree += [nd, na]
        want_ntok = self.stream_tokens or bool(self._cancels)
        if want_ntok:
            tree.append(self.state.n_tok)
        if self.stream_tokens:
            tree.append(self.state.out_buf)
        with self.spans("serve.sync"):
            vals = self._sync(tuple(tree))
        self._sync_end = time.perf_counter_ns()
        done_np, pos_np, steps_np = vals[0], vals[1], vals[2]
        k = 3
        if self.spec:
            self.spec_drafted += int(vals[3])
            self.spec_accepted += int(vals[4])
            k = 5
        ntok_np = vals[k] if want_ntok else None
        out_np = vals[k + 1] if self.stream_tokens else None
        steps_n = int(steps_np)
        self.total_steps += steps_n
        # each speculative iteration consumes spec_k fold-in keys
        self._t += steps_n * (self.spec_k if self.spec else 1)
        with self.spans("serve.fold"):
            cancelled = self._apply_cancels(staged, ntok_np) \
                if self._cancels else False
            if self.stream_tokens:
                self._emit_stream(ntok_np, out_np)
            if self.paged:
                self._reclaim_frontier(staged, pos_np)
        done_slots = [int(s) for s in np.nonzero(done_np)[0]
                      if self._slot_req[s] >= 0]
        if done_slots or cancelled:
            if done_slots:
                with self.spans("serve.finish", candidates=len(done_slots)):
                    self._finish_candidates(done_slots)
            self._schedule()
            if self.has_evidence:
                self._evid = self._gather_evid()
        elif self.chunked and (self._chunking or
                               (self._queue and self._free_slots())):
            # no completions this launch, but prefill work is waiting:
            # spend this turn's chunk budget between decode launches —
            # the stall-free interleaving the chunking exists for
            self._schedule()
        if not self.has_work():
            self._sync_end = None                # idle: no launch gap
        return True

    def pump(self) -> bool:
        """Drive ONE serving iteration (the async front-end's hook).

        Unlike ``run`` — which only admits at completion boundaries —
        ``pump`` also runs an admission pass when new work arrived
        between launches, since an open-loop arrival process delivers
        requests mid-flight. Returns False once the engine is drained
        (call again after the next ``submit``)."""
        if self.macro_steps <= 0:
            raise RuntimeError(
                "pump() drives the fused macro-step loop; construct the "
                "engine with macro_steps >= 1 for async serving")
        with self.spans("serve.pump"):
            if self._evid is None:
                self._begin()
            elif (self._queue and self._free_slots()) or self._chunking:
                self._schedule()
                if self.has_evidence and self._any_live():
                    self._evid = self._gather_evid()
            return self._step()

    def _emit_stream(self, ntok_np, out_np):
        """Queue per-slot token deltas for the async front-end. Deltas
        are emitted before finished slots fold, so a candidate's final
        tokens are never lost; the concatenation of one candidate's
        deltas is byte-identical to its finished ``tokens`` record."""
        for s in range(self.B):
            uid = int(self._slot_req[s])
            if uid < 0:
                continue
            n = int(ntok_np[s])
            if n > self._slot_streamed[s]:
                self.stream_events.append(
                    (uid, int(self._slot_cand[s]),
                     np.asarray(out_np[s][int(self._slot_streamed[s]):n])))
                self._slot_streamed[s] = n

    # ------------------------------------------------------------------
    # cancellation (the abort path)
    # ------------------------------------------------------------------
    def cancel(self, uid: int) -> bool:
        """Abort a request: queued/pending work is dropped immediately;
        running candidates are torn down at the next step boundary —
        staged frontier pages return to the pool, slots free, and the
        scheduler's worst-case commitment is refunded (see
        ``_apply_cancels``). Returns False for unknown or already-
        finished uids. A cancelled request still yields a ``Result``
        (``cancelled=True``) with whatever candidates it completed."""
        info = self._reqs.get(uid)
        if info is None:
            # mid chunked prefill: return every chunk page to the pool
            # (the job's hold) before dropping the queued request
            job = self._chunking.pop(uid, None)
            if job is not None and job["pages"]:
                self.pool.free(job["pages"])
            # queued but never prefilled: drop from the queue, with a
            # stub record so results stay uniform
            for i, r in enumerate(self._queue):
                if r.uid == uid:
                    self._queue.pop(i)
                    self._reqs[uid] = {
                        "req": r, "cache_row": None,
                        "camd": ctrl.init_state(self.camd, self.d, self.V),
                        "bias": None, "round": 0, "cand_slots": [],
                        "records": {}, "align_const": 0.0, "done": False,
                        "cancelled": True}
                    self._finish_request(uid)
                    self.cancelled_requests += 1
                    return True
            return False
        if info["done"]:
            return False
        if any(int(self._slot_req[s]) == uid for s in range(self.B)):
            # live candidates: fold the teardown into the next launch's
            # sync — the emission counts spent-accounting needs ride the
            # readback the step already pays for
            self._cancels.add(uid)
            return True
        # prefilled but not running (queued or pending a round): release
        # its prompt-cache row and page holds now
        self._queue = [r for r in self._queue if r.uid != uid]
        info["cancelled"] = True
        self._finish_request(uid)
        self.cancelled_requests += 1
        return True

    def _apply_cancels(self, staged, ntok_np) -> bool:
        """Tear down cancel-marked requests' live slots after a launch.

        Runs BEFORE ``_reclaim_frontier``: a cancelled slot's staged
        frontier pages are returned wholesale (``PagePool.return_
        frontier``) and its entry dropped from ``staged``; its
        pre-launch pages are freed, its shard's reservation released,
        and the scheduler refunds the candidate's worst-case commitment
        (tokens it did emit count as spent — the compute is burned).
        Pages/slots/budget all return to their pre-admission accounting;
        the hypothesis conservation suite pins this."""
        uids = set(self._cancels)
        self._cancels.clear()
        slots = [s for s in range(self.B)
                 if int(self._slot_req[s]) in uids]
        if not slots:
            return False
        for s in slots:
            uid = int(self._slot_req[s])
            n = int(ntok_np[s])
            self.total_tokens += n
            self.scheduler.on_cancel(uid, n, int(self._slot_lim[s]))
            self._slot_req[s] = -1
            self._slot_cand[s] = -1
            self._slot_spec[s] = 1
            self._slot_lim[s] = self.max_new
            self._slot_streamed[s] = 0
            if self.paged:
                if staged is not None and s in staged:
                    _p0, pages = staged.pop(s)
                    if pages:
                        self.pool.return_frontier(pages)
                self.pool.free(self._slot_pages[s])
                self._slot_pages[s] = []
                self._reserved_sh[self._slot_shard(s)] -= \
                    int(self._slot_reserved[s])
                self._slot_reserved[s] = 0
        # deactivate on device so later launches neither decode into the
        # dead slots nor early-exit on their stale done flags
        idx = jnp.asarray(slots)
        st = self.state
        cache = st.cache
        if self.paged:
            quar = jnp.asarray([self._quarantine(s) for s in slots],
                               jnp.int32)
            cache = {**cache,
                     "block_table": cache["block_table"].at[idx].set(
                         quar[:, None])}
        self.state = st._replace(active=st.active.at[idx].set(False),
                                 cache=cache)
        for uid in sorted(uids):
            info = self._reqs.get(uid)
            if info is not None and not info["done"]:
                info["cancelled"] = True
                self._finish_request(uid)
                self.cancelled_requests += 1
        return True

    def _run_legacy(self) -> List[Result]:
        """Pre-macro-step per-token host loop (macro_steps=0): one jitted
        step, one host sync, and one block-table scatter per generated
        token. Kept as the benchmarking baseline the fused loop is
        measured against."""
        self._schedule()
        evid = jnp.zeros((self.B, 1, self.d), jnp.float32)
        if self._evid_sharding is not None:
            evid = jax.device_put(evid, self._evid_sharding)
        if self.has_evidence:
            evid = self._gather_evid()
        while True:
            if not self._any_live():
                if self._refill_idle():
                    break
                if self.has_evidence:
                    evid = self._gather_evid()
                continue
            self.key, k = jax.random.split(self.key)
            if self.paged:
                self._alloc_step_pages()
            self._reshard()
            self.state, done = self._step_fn(self.params, self.state, k, evid)
            self.total_steps += 1
            self._t += 1
            done_np = self._sync(done)
            cancelled = self._apply_cancels(
                None, self._sync(self.state.n_tok)) \
                if self._cancels else False
            if done_np.any() or cancelled:
                # per-slot finishes, as the pre-refactor loop did — this
                # is the readback pattern the macro path amortizes away
                for s in np.nonzero(done_np)[0]:
                    if self._slot_req[int(s)] >= 0:
                        self._finish_candidates([int(s)])
                self._schedule()
                if self.has_evidence:
                    evid = self._gather_evid()
        return [self._result(uid) for uid in self._reqs]

    def _gather_evid(self):
        rows = []
        for s in range(self.B):
            uid = int(self._slot_req[s])
            if uid >= 0 and "evid_row" in self._reqs[uid]:
                rows.append(self._reqs[uid]["evid_row"][0])
            else:
                rows.append(jnp.zeros_like(
                    next(iter(self._reqs.values()))["evid_row"][0])
                    if self._reqs else jnp.zeros((1, self.d)))
        # pad rows to equal Ne
        ne = max(r.shape[0] for r in rows)
        rows = [jnp.pad(r, ((0, ne - r.shape[0]), (0, 0))) for r in rows]
        ev = jnp.stack(rows)
        if self._evid_sharding is not None:
            ev = jax.device_put(ev, self._evid_sharding)
        return ev

    def _result(self, uid: int) -> Result:
        info = self._reqs[uid]
        cs = info["camd"]
        recs = list(info["records"].values())
        if not recs:
            # budget-starved: never admitted before the stream's global
            # token budget ran out
            return Result(
                uid=uid, tokens=np.zeros((0,), np.int32), n_candidates=0,
                tokens_spent=0, rounds=info["round"],
                p_star=float(cs.p_star), best_score=float(cs.best_score),
                stopped_early=False, candidates=[],
                cancelled=info.get("cancelled", False))
        if self.mode == "self_consistency":
            # majority vote: the largest cluster wins, then its
            # best-scoring member is the answer (falling back to the
            # global best score only when cluster bookkeeping is empty)
            n_cl = int(cs.table.n_clusters)
            members: List[Dict[str, Any]] = []
            if n_cl > 0:
                sizes = np.asarray(cs.table.sizes)[:n_cl]
                best_k = int(np.argmax(sizes))
                members = [r for r in recs if r.get("cluster", -1) == best_k]
            chosen = max(members or recs, key=lambda r: r["score"])
        else:
            bu = int(cs.best_uid)
            chosen = info["records"].get(bu) or max(recs, key=lambda r: r["score"])
        return Result(
            uid=uid,
            tokens=chosen["tokens"],
            n_candidates=len(recs),
            tokens_spent=int(sum(r["n"] for r in recs)),
            rounds=info["round"],
            p_star=float(cs.p_star),
            best_score=float(cs.best_score),
            stopped_early=(self.mode == "camd" and bool(cs.stopped)
                           and float(cs.p_star) >= 1.0 - self.camd.delta),
            candidates=[{k: v for k, v in r.items() if k not in ("counts", "emb")}
                        for r in recs],
            cancelled=info.get("cancelled", False),
        )


class _EngineSchedContext(SchedulerContext):
    """The engine-side implementation of the scheduler facade. Slot ids
    are handed out in ascending order (``_free_slots``) exactly as the
    pre-scheduler loop did, so the fifo policy's slot assignment — and
    therefore its token streams — stay bit-identical."""

    def __init__(self, eng: ServeEngine):
        self.eng = eng
        self.max_new = eng.max_new
        self.num_shards = eng.dp

    def free_slots(self) -> int:
        return len(self.eng._free_slots())

    def queued_new(self) -> List[NewWork]:
        eng = self.eng
        out = []
        for r in eng._queue:
            if r.uid in eng._chunking:
                continue                 # mid chunked prefill: not yet
                                         # admissible, but later short
                                         # requests must keep streaming
            if r.uid not in eng._reqs:
                break                    # prefill covers a queue prefix
            info = eng._reqs[r.uid]
            out.append(NewWork(uid=r.uid, arrival=eng._arrival[r.uid],
                               want=eng._per_round(),
                               prompt_len=info.get("prompt_len", 0),
                               evidence_entropy=info.get(
                                   "evidence_entropy", 0.0)))
        return out

    def pending_rounds(self) -> List[RoundWork]:
        eng = self.eng
        out = []
        for uid, info in eng._reqs.items():
            if info["done"] or info.get("pending_round") is not True:
                continue
            recs = list(info["records"].values())
            scores = [r["score"] for r in recs]
            out.append(RoundWork(
                uid=uid, arrival=eng._arrival.get(uid, 0),
                want=eng._needed(info), rounds=info["round"],
                p_star=info.get("p_star", 0.0), delta=eng.camd.delta,
                best_score=info.get("best_score_host",
                                    max(scores, default=0.0)),
                scores=scores,
                mean_len=float(np.mean([r["n"] for r in recs]))
                if recs else 0.0))
        return out

    def affordable(self, uid: int, want: int, limit: int) -> int:
        eng = self.eng
        if not eng.paged:
            return want
        return eng._paged_affordable(eng._reqs[uid], want, limit)

    def admit_new(self, uid: int, take: int, limit: int) -> None:
        eng = self.eng
        i = next(i for i, r in enumerate(eng._queue) if r.uid == uid)
        self._admit(eng._queue.pop(i), take, limit)

    def admit_round(self, uid: int, take: int, limit: int) -> None:
        info = self.eng._reqs[uid]
        info["pending_round"] = False
        self._admit(info["req"], take, limit)

    def _admit(self, req: Request, take: int, limit: int) -> None:
        eng = self.eng
        with eng.spans("serve.admit", uid=req.uid, candidates=take):
            eng._admit(req, eng._free_slots()[:take], limit=limit)

    def finish_request(self, uid: int) -> None:
        self.eng._finish_request(uid)
