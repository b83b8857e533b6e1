"""The closed loop's callers and the prompt lengths: every seed gets the
same multiset of lengths, in another order."""
import numpy as np

from bench.gen import closed, lognormal

SPEC = {"median": 96, "sigma": 0.6, "lo": 32, "hi": 256}


def test_lengths_are_a_fixed_multiset():
    ln = [lognormal.draw(SPEC, 101, np.random.default_rng(s))
          for s in (3, 4, 2 ** 31 + 9)]
    assert sorted(ln[0]) == sorted(ln[1]) == sorted(ln[2])
    assert not np.array_equal(ln[0], ln[1])
    assert np.median(ln[0]) == 96
    assert ln[0].min() >= 32 and ln[0].max() <= 256


def test_support_covers_every_length_drawn():
    sup = set(lognormal.support(SPEC))
    assert sup == set(range(32, 257))
    assert set(lognormal.draw(SPEC, 512, np.random.default_rng(0))) <= sup


def test_callers_start_apart_and_the_window_follows():
    spec = {"clients": 12, "stagger_steps": 64, "window_steps": 1152}
    starts = [closed.start_step(spec, c) for c in range(spec["clients"])]
    assert starts == [64 * c for c in range(12)]
    assert closed.window_step(spec) > max(starts)
