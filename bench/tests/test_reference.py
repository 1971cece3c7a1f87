"""The plain reference against a loop written from the description, at a
small geometry: one cycle, one channel, one bit at a time."""

import dataclasses

import jax
import numpy as np
import pytest

from bench import reference

GEO = reference.Geometry(variant="sparse_compim", channels=4, lbp_bits=3,
                         dim=64, segments=4, window=8, n_classes=2,
                         class_density=0.5)


def loop_counts(bank, owner, frame, geo):
    """Temporal counts of one frame, bit by bit."""
    item, elec = (np.asarray(bank[k][owner]) for k in ("item", "elec"))
    counts = np.zeros(geo.dim, np.int64)
    for codes in frame:
        if geo.sparse:
            hv = np.zeros(geo.dim, bool)
            for c, code in enumerate(codes):
                for s in range(geo.segments):
                    p = (int(item[c, code, s]) + int(elec[c, s])) % geo.seg_len
                    hv[s * geo.seg_len + p] = True
        else:
            ones = np.zeros(geo.dim, np.int64)
            for c, code in enumerate(codes):
                for d in range(geo.dim):
                    w, b = divmod(d, 32)
                    ones[d] += ((int(item[c, code, w]) ^ int(elec[c, w]))
                                >> b) & 1
            hv = 2 * ones > geo.channels
        counts += hv
    return counts


@pytest.mark.parametrize("variant", ["sparse_compim", "dense"])
def test_counts_and_decisions_match_the_loop(variant):
    geo = dataclasses.replace(GEO, variant=variant)
    bank = reference.make_bank(jax.random.PRNGKey(3), geo, 2)
    rng = np.random.default_rng(0)
    frames = rng.integers(0, geo.codes, (5, geo.window, geo.channels),
                          np.uint8)
    owner = np.array([0, 1, 1, 0, 1], np.int32)
    want = np.stack([loop_counts(bank, o, f, geo)
                     for o, f in zip(owner, frames)])
    got = reference.counts_of_frames(bank, owner, frames, geo)
    assert np.array_equal(got, want)

    thresholds = np.array([3, 5], np.int32)
    class_hvs = rng.integers(0, 2**32, (2, 2, geo.words), np.uint64
                             ).astype(np.uint32)
    hvs, scores, preds = reference.decisions(bank, thresholds, class_hvs,
                                             owner, frames, geo)
    for k, (o, c) in enumerate(zip(owner, want)):
        bits = c >= thresholds[o] if geo.sparse else 2 * c > geo.window
        packed = [sum(int(bits[w * 32 + b]) << b for b in range(32))
                  for w in range(geo.words)]
        assert list(hvs[k]) == packed
        cls = [[(int(class_hvs[o, j, d // 32]) >> (d % 32)) & 1
                for d in range(geo.dim)] for j in range(2)]
        s = [sum(int(b) & x for b, x in zip(bits, cl)) if geo.sparse else
             geo.dim - sum(int(b) ^ x for b, x in zip(bits, cl))
             for cl in cls]
        assert list(scores[k]) == s
        assert preds[k] == (1 if s[1] > s[0] else 0)


def test_thin_threshold_keeps_at_most_the_density():
    counts = np.array([0, 1, 1, 2, 3, 3, 3, 7])
    assert reference.thin_threshold(counts, 0.5) == 3
    assert reference.thin_threshold(counts, 0.125) == 4
    assert reference.thin_threshold(counts, 0.0) == 8
    assert reference.thin_threshold(np.zeros(4), 0.5) == 1


def test_train_gives_each_class_its_frames():
    rng = np.random.default_rng(1)
    bank = reference.make_bank(jax.random.PRNGKey(0), GEO, 2)
    codes = rng.integers(0, GEO.codes, (2, 6, GEO.window, GEO.channels),
                         np.uint8)
    labels = np.array([[0, 0, 0, 1, 1, 1]] * 2, np.int32)
    thr, chv = reference.train(bank, codes, labels, [0.2, 0.3], GEO)
    assert thr.shape == (2,) and (thr >= 1).all()
    assert chv.shape == (2, 2, GEO.words) and chv.any()
    with pytest.raises(ValueError, match="no frames"):
        reference.train(bank, codes, np.zeros_like(labels), [0.2, 0.3], GEO)
