"""Rate and window arithmetic on fake counters, and the readers built on
it."""
import numpy as np
import pytest

from bench.harness import _pack, read_metric


def ctx_of(**kw):
    ctx = {"requests": [], "window_s": 10.0, "live0": 0,
           "counters": {"tokens": 0, "steps": 0, "launches": 0,
                        "slots": 64, "sched": {"admitted_candidates": 0}},
           "setup_s": 1.0, "trace": None}
    ctx.update(kw)
    return ctx


def test_decode_rate_counts_live_tokens_at_both_edges():
    # 400 tokens finished in the window, 300 live at its close, 100 live
    # (already decoded) at its opening; 4 candidates admitted in it
    ctx = ctx_of(live0=100, counters={
        "tokens": 700, "steps": 50, "launches": 10, "slots": 8,
        "sched": {"admitted_candidates": 4}})
    assert read_metric("decode_tokens_per_s", ctx)[0] == 60.0
    assert read_metric("steps_per_launch.tokens", ctx)[0] == 5.0


def test_slot_occupancy_leaves_out_tokens_sampled_at_admission():
    # 8 slots, 50 steps: at most 400 decoded tokens; 600 produced in the
    # window, 200 of them first tokens sampled when their candidates
    # were admitted
    ctx = ctx_of(counters={"tokens": 600, "steps": 50, "launches": 10,
                           "slots": 8, "sched": {"admitted_candidates": 200}})
    assert read_metric("slot_occupancy.tokens", ctx)[0] == 100.0
    ctx["counters"]["sched"]["admitted_candidates"] = 300
    assert read_metric("slot_occupancy.tokens", ctx)[0] == 75.0


def test_silent_without_steps_or_launches():
    ctx = ctx_of()
    assert read_metric("slot_occupancy.tokens", ctx)[0] is None
    assert read_metric("steps_per_launch.tokens", ctx)[0] is None


def test_pool_peak():
    c = {"tokens": 0, "steps": 0, "launches": 0, "slots": 8,
         "sched": {"admitted_candidates": 0},
         "kv": {"max_in_use": 30, "num_pages": 120}}
    assert read_metric("kv_pages_peak_share.tokens",
                       ctx_of(counters=c))[0] == 25.0


def test_trace_readers_are_silent_without_a_trace():
    ctx = ctx_of()
    for name in ("mfu.tokens", "device_idle_share.tokens",
                 "paged_decode_roofline.tokens"):
        assert read_metric(name, ctx)[0] is None


SIZES = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 100,
         "tie_embeddings": True, "mlp": "swiglu"}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_roofline_share_from_required_work_over_kernel_time():
    ctxs = [np.array([10, 20]), np.array([11, 21])]
    flops, nbytes = 0.0, 0.0
    for c in ctxs:
        # 2 layers; K+V of 2 kv heads x 16 x 2 B per token; Q and O
        nbytes += 2 * (c.sum() * 2 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2)
    t_meas = 2 * (nbytes / 1e9)         # the kernel took twice the least
    ctx = ctx_of(trace={"kernel_s": {"paged_decode": t_meas},
                        "busy_s": 0.5, "window_s": 2.0},
                 sizes=SIZES, peak=PEAK, decode_ctxs=ctxs, prefills=[],
                 trace_s=2.0)
    assert read_metric("paged_decode_roofline.tokens", ctx)[0] == \
        pytest.approx(50.0)
    assert read_metric("device_idle_share.tokens", ctx)[0] == 75.0


def test_roofline_is_silent_where_the_kernel_never_ran():
    ctx = ctx_of(trace={"kernel_s": {"paged_decode": 0.0}, "busy_s": 1,
                        "window_s": 2.0},
                 sizes=SIZES, peak=PEAK, decode_ctxs=[np.array([3])],
                 prefills=[], trace_s=2.0)
    assert read_metric("paged_decode_roofline.tokens", ctx)[0] is None


def test_mfu_counts_decode_and_prefill_flops():
    ctxs = [np.array([5, 7])]
    # 2 tokens: each 2*params*2 layers + head; attention 4*H*hd per pair
    dec = 2 * (2 * 2 * (64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128)
               + 2 * 64 * 100) + 2 * 4 * 4 * 16 * (5 + 7)
    pre = (2 * 2 * (64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128) * 3
           + 2 * 4 * 4 * 16 * 6 + 2 * 64 * 100)
    ctx = ctx_of(trace={"kernel_s": {}, "busy_s": 1, "window_s": 1},
                 sizes=SIZES, peak=PEAK, decode_ctxs=ctxs, prefills=[3],
                 trace_s=1.0)
    assert read_metric("mfu.tokens", ctx)[0] == pytest.approx(
        100 * (dec + pre) / 1e12)


@pytest.mark.parametrize("kmax,slots", [(12, 24), (8, 8), (4, 8), (1, 4)])
def test_fills_hold_every_finish_count_once(kmax, slots):
    fills = _pack([(k, k) for k in range(1, kmax + 1)], slots)
    assert sorted(k for f in fills for k, _ in f) == list(range(1, kmax + 1))
    assert all(sum(n for _, n in f) <= slots for f in fills)


def test_prefill_batches_never_merge_a_bucket():
    items = [(b, nb) for b in (32, 64, 128, 256) for nb in (1, 2, 4, 8)]
    batches = _pack(items, 8)
    assert sorted(x for b in batches for x in b) == sorted(items)
    for b in batches:
        assert sum(n for _, n in b) <= 8
        assert len({k for k, _ in b}) == len(b)


def test_mfu_adds_tower_and_unwrapped_prefill_work():
    base = ctx_of(trace={"kernel_s": {}, "busy_s": 1, "window_s": 1},
                  sizes=SIZES, peak=PEAK, decode_ctxs=[np.array([5])],
                  prefills=[3], trace_s=1.0)
    text = read_metric("mfu.tokens", base)[0]
    # the text cell's counters: every prefill token wrapped, no image
    base["trace_sched"] = {"image_encodes": 0, "prefill_tokens": 3}
    assert read_metric("mfu.tokens", base)[0] == text
    sizes = dict(SIZES, num_evidence_tokens=4, vision={
        "image_h": 8, "image_w": 8, "channels": 3, "patch": 4,
        "num_layers": 1, "d_model": 16, "num_heads": 2, "d_ff": 32,
        "projector": [[16, 64]]})
    tower = (2 * 4 * 48 * 16 + 4 * (8 * 16 * 16 + 4 * 16 * 32)
             + 4 * 4 * 4 * 16 + 2 * 4 * 16 * 64)
    ctx = dict(base, sizes=sizes,
               trace_sched={"image_encodes": 3, "prefill_tokens": 3 + 10})
    # 3 encodes, and 10 prefill tokens on paths the Recorder does not
    # wrap, through the layers' matrices: 2 x 2 layers x params
    extra = 3 * tower + 10 * 2 * 2 * (64 * 64 * 2 + 64 * 32 * 2
                                      + 3 * 64 * 128)
    assert read_metric("mfu.tokens", ctx)[0] == pytest.approx(
        text + 100 * extra / 1e12)
