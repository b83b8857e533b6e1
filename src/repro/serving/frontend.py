"""Async streaming front-end for ``ServeEngine``.

Request stream in, token stream out: ``submit`` enqueues a request into
the engine between macro-step launches, ``stream`` yields its tokens as
the engine emits them, ``cancel`` aborts it mid-stream (pages returned,
slot freed, scheduler commitment refunded — see
``ServeEngine.cancel``), and ``result`` resolves to the request's final
``Result``.

Design: one cooperative asyncio task (``_pump_loop``) owns the engine.
Each iteration runs exactly one ``ServeEngine.pump()`` — one macro
launch plus its host-side fold — then drains the engine's stream-event
and completion feeds into per-request ``asyncio.Queue``s and yields the
event loop, so client coroutines (arrival timers, stream consumers,
cancellers) run *between* launches. jax dispatch stays single-threaded
(the donated-buffer decode state is not thread-safe), which also makes
cancellation race-free by construction: a ``cancel`` always lands at a
step boundary, exactly where the engine applies it.

Token streams are **incremental** (per-launch deltas, riding the launch
sync — zero extra host syncs) when the engine decodes a single greedy
candidate per request; multi-candidate modes (camd/best_of_n/self_
consistency) choose their answer only at completion, so their streams
deliver the chosen candidate's tokens when the request finishes. In
both cases the stream's concatenation is byte-identical to the
synchronous ``run()`` result (pinned by ``tests/test_async_frontend``).

TTFT under load: with chunked prefill on (``ServeEngine(prefill_
chunk=...)``) the pump loop interleaves at most one chunk budget of
prefill work per launch, so a long prompt no longer monopolizes the
engine between macro steps — short requests' first tokens (and the
long request's own TTFT, which starts at its *final* chunk rather
than a monolithic whole-prompt prefill) stop queueing behind
whole-prompt prefills.
"""
from __future__ import annotations

import asyncio
from typing import Dict, Optional, Set

import numpy as np

from repro.serving.engine import Request, Result, ServeEngine

_DONE = object()          # stream-termination sentinel


class AsyncServeFrontend:
    """Asyncio front-end over one ``ServeEngine`` (macro-step loop).

    Usage::

        async with AsyncServeFrontend(engine) as fe:
            await fe.submit(Request(uid=0, prompt=...))
            async for tok in fe.stream(0):
                ...
            res = await fe.result(0)
    """

    def __init__(self, engine: ServeEngine, *, stream_tokens: bool = True):
        if engine.macro_steps <= 0:
            raise ValueError(
                "AsyncServeFrontend drives the fused macro-step loop; "
                "construct the engine with macro_steps >= 1")
        self.engine = engine
        # incremental per-launch deltas only make sense when the single
        # candidate IS the answer; other modes pick at completion
        self._incremental = bool(stream_tokens) \
            and engine.mode == "greedy" and engine.n_candidates == 1
        engine.stream_tokens = self._incremental
        self._queues: Dict[int, asyncio.Queue] = {}
        self._futs: Dict[int, asyncio.Future] = {}
        self._closed: Set[int] = set()
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._error: Optional[BaseException] = None

    # -- lifecycle ------------------------------------------------------
    async def start(self) -> "AsyncServeFrontend":
        if self._task is None:
            self._wake = asyncio.Event()
            self._task = asyncio.create_task(self._pump_loop())
        return self

    async def close(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
        # leave the engine reusable for synchronous run(): nothing left
        # to drain the stream feed once the front-end is gone
        self.engine.stream_tokens = False
        self.engine.stream_events.clear()

    async def __aenter__(self) -> "AsyncServeFrontend":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    # -- client API -----------------------------------------------------
    async def submit(self, req: Request) -> int:
        """Enqueue a request; admission happens at the next pump."""
        self._require_ok()
        self.engine.submit(req)
        self._queues[req.uid] = asyncio.Queue()
        self._futs[req.uid] = asyncio.get_running_loop().create_future()
        self._wake.set()
        return req.uid

    async def stream(self, uid: int):
        """Async iterator of the request's output tokens (ints). Ends
        when the request completes or is cancelled; already-emitted
        tokens are always delivered."""
        q = self._queues[uid]
        while True:
            tok = await q.get()
            if tok is _DONE:
                return
            yield tok

    async def result(self, uid: int) -> Result:
        """The request's final ``Result`` (``cancelled=True`` if it was
        aborted)."""
        return await self._futs[uid]

    async def cancel(self, uid: int) -> bool:
        """Abort ``uid``: closes its stream immediately (queued tokens
        still deliverable) and tears its engine state down at the next
        step boundary — frontier pages returned, slot freed, scheduler
        commitment refunded."""
        ok = self.engine.cancel(uid)
        self._close_stream(uid)
        if self._wake is not None:
            self._wake.set()       # deferred teardown needs a pump
        return ok

    async def join(self) -> None:
        """Wait until every submitted request has a result."""
        if self._futs:
            await asyncio.gather(*self._futs.values())

    # -- pump -----------------------------------------------------------
    async def _pump_loop(self) -> None:
        try:
            while True:
                if self.engine.has_work():
                    self.engine.pump()
                    self._dispatch()
                    # one event-loop turn between launches: arrivals,
                    # stream consumers and cancels run here
                    await asyncio.sleep(0)
                else:
                    self._dispatch()   # flush direct-cancel completions
                    self._wake.clear()
                    if self.engine.has_work():
                        continue       # raced with a submit
                    await self._wake.wait()
        except asyncio.CancelledError:
            raise
        except BaseException as e:     # surface on every waiter
            self._error = e
            self._fail_all(e)

    def _dispatch(self) -> None:
        eng = self.engine
        with eng.spans("serve.dispatch"):
            for uid, _cand, toks in eng.drain_stream_events():
                q = self._queues.get(uid)
                if q is None or uid in self._closed:
                    continue
                for t in np.asarray(toks).tolist():
                    q.put_nowait(int(t))
            for uid in eng.pop_finished():
                fut = self._futs.get(uid)
                if fut is None:
                    continue               # finished outside this front-end
                res = eng.result(uid)
                if not fut.done():
                    fut.set_result(res)
                q = self._queues.get(uid)
                if q is not None and uid not in self._closed \
                        and not self._incremental and not res.cancelled:
                    for t in np.asarray(res.tokens).tolist():
                        q.put_nowait(int(t))
                self._close_stream(uid)

    # -- internals ------------------------------------------------------
    def _close_stream(self, uid: int) -> None:
        if uid in self._closed:
            return
        self._closed.add(uid)
        q = self._queues.get(uid)
        if q is not None:
            q.put_nowait(_DONE)

    def _fail_all(self, e: BaseException) -> None:
        for fut in self._futs.values():
            if not fut.done():
                fut.set_exception(e)
        for uid in list(self._queues):
            self._close_stream(uid)

    def _require_ok(self) -> None:
        if self._error is not None:
            raise RuntimeError("serving pump failed") from self._error
        if self._task is None:
            raise RuntimeError("front-end not started "
                               "(use 'async with' or await start())")
