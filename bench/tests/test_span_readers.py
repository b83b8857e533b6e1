"""The readers of the engine's host-loop counters, on hand-built
contexts: the mean launch gap (over the traced window) and the mean
queue wait (over the whole window), each silent where its base is 0 or
where the program has no such counter."""
import pytest

from bench.harness import read_metric


def counters(**sched):
    return {"tokens": 0, "steps": 0, "launches": 0, "slots": 8,
            "sched": dict(admitted_candidates=0, **sched)}


def ctx_of(**sched):
    return {"counters": counters(**sched), "trace_counters": counters(**sched)}


def test_launch_gap_is_the_mean_gap_of_the_traced_window_in_ms():
    ctx = ctx_of(launch_gap_ns=3 * 25_000_000, launch_gaps=3)
    # the whole window's gaps hold the profiler's stop: not read
    ctx["counters"]["sched"]["launch_gap_ns"] *= 100
    assert read_metric("launch_gap_ms.tokens", ctx) == (
        pytest.approx(25.0), "ms")
    del ctx["trace_counters"]
    assert read_metric("launch_gap_ms.tokens", ctx)[0] is None


def test_queue_wait_is_the_mean_wait_of_first_admissions_in_ms(capsys):
    ctx = ctx_of(queue_wait_ns=2 * 70_000_000_000 + 1_000_000,
                 first_admissions=2)
    assert read_metric("queue_wait_ms.tokens", ctx) == (
        pytest.approx(70_000.5), "ms")
    assert "over 2 first admissions" in capsys.readouterr().err


@pytest.mark.parametrize("name,sched", [
    ("launch_gap_ms.tokens", {"launch_gap_ns": 0, "launch_gaps": 0}),
    ("queue_wait_ms.tokens", {"queue_wait_ns": 0, "first_admissions": 0}),
    ("launch_gap_ms.tokens", {}),        # a program without the counters
    ("queue_wait_ms.tokens", {}),
])
def test_silent_without_a_base(name, sched):
    assert read_metric(name, ctx_of(**sched))[0] is None
