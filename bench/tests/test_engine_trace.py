"""``trace_reduce`` on a trace of the serving engine recorded on a TPU
v5e by ``make_engine_trace.py``: three requests served through the async
front end, each pump inside the harness's ``pump`` span, all inside a
``window`` span.

The engine's ``serve.*`` spans sit on the host plane, nested in the
pumps. The accepted reduction labels idle gaps by the harness's own
spans only; the same reduction with the engine's spans among its labels
puts the idle time down to engine phases, and moves neither the window,
the busy time nor any per-operation sum.
"""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

HERE = Path(__file__).resolve().parent
# not under data/: ``trace_reduce.load`` takes the newest trace there
TRACE = HERE / "engine_trace" / "engine.xplane.pb"
KERNELS = json.loads((HERE.parent / "configs" / "qwen3-0.6b.json")
                     .read_text())["kernels"]
SERVE = ("serve.pump", "serve.schedule", "serve.prefill", "serve.admit",
         "serve.stage", "serve.launch", "serve.sync", "serve.fold",
         "serve.finish", "serve.dispatch")


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


@pytest.fixture(scope="module")
def host(pd):
    return [(e.name, e.start_ns, e.end_ns) for p in pd.planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events]


def test_engine_spans_nest_in_the_pumps(host):
    assert set(SERVE) <= {n for n, _, _ in host}
    wrapped = [(a, b) for n, a, b in host if n == "pump"]
    pumps = [(a, b) for n, a, b in host if n == "serve.pump"]
    assert pumps and all(any(wa <= a and b <= wb for wa, wb in wrapped)
                         for a, b in pumps)
    for n, a, b in host:
        if n in SERVE and n not in ("serve.pump", "serve.dispatch"):
            assert any(pa <= a and b <= pb for pa, pb in pumps), n


def test_accepted_labels_are_the_harness_spans(pd):
    gaps = dict(trace_reduce.reduce(pd, KERNELS)["idle_gaps"])
    assert set(gaps) <= {"host:pump", "host:submit", "host:stream",
                         "host:other"}


def test_idle_gaps_put_down_to_engine_phases(pd, monkeypatch):
    base = trace_reduce.reduce(pd, KERNELS, top=50)
    monkeypatch.setattr(trace_reduce, "HOST_SPANS",
                        trace_reduce.HOST_SPANS + SERVE)
    r = trace_reduce.reduce(pd, KERNELS, top=50)
    for k in ("window_s", "busy_s", "kernel_s", "device_ops"):
        assert r[k] == base[k], k
    gaps = dict(r["idle_gaps"])
    idle = r["window_s"] - r["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    serve = sum(v for k, v in gaps.items() if k.startswith("host:serve."))
    # what the harness's pump span held now carries an engine phase
    assert serve >= 0.8 * dict(base["idle_gaps"])["host:pump"]


def test_device_modules_carry_the_program_names(pd):
    mods = {e.name.split("(")[0] for p in pd.planes
            if p.name.startswith("/device:TPU:") for ln in p.lines
            if ln.name == "XLA Modules" for e in ln.events}
    assert {"jit_decode_launch", "jit_prefill_bucket"} <= mods
