"""``trace_reduce`` on a small trace recorded on a TPU v5e by
``make_trace.py``: three ``pump`` spans inside a ``window`` span, each
running the paged decode kernel, the flash kernel and an XLA matmul."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

HERE = Path(__file__).resolve().parent
TRACE = HERE / "data" / "small.xplane.pb"
KERNELS = json.loads((HERE.parent / "configs" / "qwen3-0.6b.json")
                     .read_text())["kernels"]


@pytest.fixture(scope="module")
def reduced():
    pd = trace_reduce.load(str(TRACE.parent))
    assert pd is not None
    return trace_reduce.reduce(pd, KERNELS)


def test_busy_within_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_each_kernel_found(reduced):
    for name in KERNELS:
        assert reduced["kernel_s"][name] > 0, name
    assert sum(reduced["kernel_s"].values()) < reduced["busy_s"]


def test_breakdown_lists(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(t > 0 for _, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"host:pump", "host:submit", "host:stream",
                         "host:other"}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert gaps.get("host:other", 0) > 0     # the sleeps between pumps
