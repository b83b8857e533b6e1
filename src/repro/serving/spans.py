"""Named host spans for the serving engine.

A span is a context manager around one phase of the host loop
(``serve.pump``, ``serve.schedule``, ``serve.launch``, ...). Each one

- opens a ``jax.profiler.TraceAnnotation``, so under the profiler it
  lands on the host plane, on the same clock as the device's operations;
- adds its ``perf_counter_ns`` duration to a per-name aggregate of
  count, total and max.

The profiler is the only switch: with it off a span costs two clock
reads and a dict update. Spans nest on the host thread; for the
outermost span (a root, such as one pump) the time of each direct child
is kept until the next root of that name opens, so a long pump can say
which phase held it.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List

import jax


class Spans:
    def __init__(self):
        self._open: List[str] = []
        self._children: Dict[str, int] = {}
        self.reset()

    def reset(self) -> None:
        """Zero the aggregates (spans open now still close normally)."""
        self.agg: Dict[str, List[int]] = {}   # name -> [count, total, max] ns
        self.last: Dict[str, Dict[str, Any]] = {}   # root -> its last run

    @contextlib.contextmanager
    def __call__(self, name: str, **args):
        if not self._open:
            self._children = {}
        self._open.append(name)
        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield
        finally:
            dt = time.perf_counter_ns() - t0
            self._open.pop()
            a = self.agg.setdefault(name, [0, 0, 0])
            a[0] += 1
            a[1] += dt
            a[2] = max(a[2], dt)
            if len(self._open) == 1:
                self._children[name] = self._children.get(name, 0) + dt
            elif not self._open:
                self.last[name] = {"ns": dt, "children": self._children}

    def stats(self) -> Dict[str, Any]:
        """Per-name ``count``/``total_ns``/``max_ns``, and for each root
        its most recent run: ``ns`` and its direct children's ns."""
        return {"spans": {n: {"count": c, "total_ns": t, "max_ns": m}
                          for n, (c, t, m) in self.agg.items()},
                "last": {n: {"ns": r["ns"], "children": dict(r["children"])}
                         for n, r in self.last.items()}}
