"""From a profiler trace (``jax.profiler.ProfileData``) to device busy
and idle time, per-operation and per-kernel device time, and the idle
gaps labelled by the harness's own host spans.

The window is the harness's ``window`` span on the host. Device
operations are the events of each ``/device:TPU:<n>`` plane's
``XLA Ops`` line; busy time is the union of their intervals inside the
window, averaged over the devices.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("pump", "submit", "stream", "window")


def profile_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce(pd, kernels: Dict[str, str], top: int = 10) -> Optional[dict]:
    """``kernels``: name -> regex over an op's HLO text (which starts
    with its instruction name, e.g. ``%flash_attention.3 = ...``).
    Returns None when the trace holds no device operation."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            devices.append([(e.name, e.start_ns, e.end_ns)
                            for ln in ops for e in ln.events])
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.end_ns) for ln in plane.lines
                     for e in ln.events if e.name in HOST_SPANS]
    devices = [d for d in devices if d]
    if not devices:
        return None
    win = [(a, b) for n, a, b in host if n == "window"]
    lo, hi = win[0] if win else (min(e[1] for d in devices for e in d),
                                 max(e[2] for d in devices for e in d))
    pats = {k: re.compile(p) for k, p in kernels.items()}
    per_op: Dict[str, float] = {}
    per_kernel = {k: 0.0 for k in kernels}
    busy_ns, gaps = 0.0, []
    for d in devices:
        iv = []
        for name, a, b in d:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            iv.append((a, b))
            op = name.split(" = ")[0]
            per_op[op] = per_op.get(op, 0.0) + (b - a) / len(devices)
            for k, p in pats.items():
                if p.search(name):
                    per_kernel[k] += (b - a) / len(devices)
        merged = _union(iv)
        busy_ns += sum(b - a for a, b in merged) / len(devices)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    idle: Dict[str, float] = {}
    spans = sorted(((b - a, n, a, b) for n, a, b in host if n != "window"))
    for a, b in gaps:
        mid = (a + b) / 2
        label = next((n for _, n, s, e in spans if s <= mid <= e), "other")
        idle[f"host:{label}"] = idle.get(f"host:{label}", 0.0) \
            + (b - a) / len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "kernel_s": {k: v * 1e-9 for k, v in per_kernel.items()},
        "device_ops": [[n, v * 1e-9] for n, v in
                       sorted(per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, v * 1e-9] for n, v in
                      sorted(idle.items(), key=lambda x: -x[1])[:top]],
    }
