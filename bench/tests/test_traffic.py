"""The traffic generator: by-name mixes, staggered frame phases, and the
same sizes for every seed."""

import numpy as np

from bench.tests import tiny
from bench.traffic import Traffic, training_frames


def mix(name, **over):
    m = tiny.load_mix(name)
    m.update(over)
    return m


def make(name, seed=1, sessions=64, **over):
    m = mix(name, record={"pre_s": 1.0, "ictal_s": 1.0, "post_s": 0.5},
            **over)
    return Traffic(m, sessions=sessions, patients=16, channels=8,
                   lbp_bits=6, rng=np.random.default_rng(seed))


def completions(t, window=256, pushes=24):
    """Sessions that complete a frame on each push after warm-up."""
    return [int(((t.sent(j + 1) // window) - (t.sent(j) // window)).sum())
            for j in range(t.warm_pushes, t.warm_pushes + pushes)]


def test_packets_an_eighth_decide_each_tick():
    t = make("packets", sessions=64)
    assert t.warm_pushes == 8
    assert completions(t) == [8] * 24
    # every session completes exactly one frame in each 8 ticks
    done = t.sent(t.warm_pushes + 8) // 256 - t.sent(t.warm_pushes) // 256
    assert (done == 1).all()


def test_packets_warm_up_starts_session_i_at_tick_i_mod_8():
    t = make("packets", sessions=16)
    for j in range(8):
        ll = t.lengths(j)
        want = np.where(np.arange(16) % 8 <= j, 32, 0)
        assert ll is None and j == 7 or np.array_equal(ll, want)


def test_frames_every_session_decides_every_tick():
    assert completions(make("frames", sessions=64)) == [64] * 24


def test_seed_draws_signal_not_sizes():
    a, b, a2 = make("frames", seed=1), make("frames", seed=2), make(
        "frames", seed=1)
    assert a.pool.shape == b.pool.shape and a.cycles == b.cycles
    assert not np.array_equal(a.pool, b.pool)
    assert np.array_equal(a.pool, a2.pool) and a.checked == a2.checked
    assert 0 in a.checked and 63 in a.checked


def test_stream_is_what_the_pushes_carried():
    t = make("packets", sessions=16)
    i, pushes = 5, 40
    got = np.concatenate([t.batch(j)[i] for j in range(pushes)
                          if (t.lengths(j) is None or t.lengths(j)[i])])
    assert np.array_equal(t.stream(i, pushes), got)
    assert len(got) == t.sent(pushes)[i]


def test_training_frames_hold_both_classes():
    codes, labels = training_frames(
        np.random.default_rng(0), patients=2, channels=8, lbp_bits=6,
        window=256, record={"pre_s": 2.0, "ictal_s": 2.0, "post_s": 0.0})
    assert codes.shape[:2] == labels.shape and codes.shape[2:] == (256, 8)
    assert set(np.unique(labels)) == {0, 1}
    assert codes.max() < 64
