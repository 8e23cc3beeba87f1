"""The reference follows an eq.-(3) bin or a draw that rounding decides,
and nothing wider."""

import numpy as np

from chipbench.reference import alg1
from chipbench.reference.alg1 import BINS, Alg1, best_branch, nearest_bins


def test_nearest_bins_near_a_midpoint():
    mid = float(np.sqrt(4000.0 * 7000.0))          # 5291.5 s
    i4, i7 = (int(np.flatnonzero(BINS == b)[0]) for b in (4000, 7000))
    assert sorted(nearest_bins(mid * 1.0001, alg1.BIN_MARGIN)) == [i4, i7]
    assert nearest_bins(mid * 1.0001, alg1.BIN_MARGIN)[0] == i7
    assert nearest_bins(mid * 1.01, alg1.BIN_MARGIN) == [i7]


def _learn_all(waits, gumbels, picks=()):
    est = Alg1(None, picks)
    for w, g in zip(waits, gumbels):
        est.learn(w, g)
    return est


def test_best_branch_finds_the_program_side_of_a_tie():
    rng = np.random.default_rng(3)
    gumbels = rng.gumbel(size=(6, BINS.size)).astype(np.float32)
    waits = [5291.6, 5291.6, 300.0, 5291.6, 5291.6, 1200.0]
    exact = _learn_all(waits, gumbels)
    assert len(exact.ties) >= 4
    # a "program" that rounded the other way at its second tie
    other = _learn_all(waits, gumbels, picks=(0, 1))

    def score(est):
        return int(np.abs(est.C - other.C).max() + np.abs(est.R - other.R).max())

    est, picks = best_branch(lambda p: _learn_all(waits, gumbels, p), score)
    assert score(est) == 0 and any(picks)
    assert score(exact) > 0      # the exact replay alone would not match
