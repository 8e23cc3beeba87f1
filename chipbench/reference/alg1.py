"""Algorithm 1 of arXiv 2401.09733 (tuned §4.5 policy, γ = 1), exactly.

With γ = 1 every update subtracts a whole number of nats from ``log p``:
a closed round subtracts its accumulated 0/1 losses, the full-information
pass subtracts the eq.-(3) loss vector (γ/50 · ℓ · 50 = ℓ). So the
posterior is ``p_a ∝ exp(-C_a)`` with integer counts ``C``, and the
reference keeps ``C`` instead of a rounded ``log p``. The MAP is then
exact: the bins with the least ``C``. Where several bins share it, a
float implementation's argmax is decided by rounding, except between
bins whose loss histories are identical (their floats are equal, so the
lowest index wins). :meth:`Alg1.map_choices` returns the lowest index of
each history class among the tied bins: every answer a sound float32
implementation can give.

Two more choices of Algorithm 1 are decided by rounding where they lie
near a tie: the bin nearest the observed wait in log space (eq. (3)),
and the line-4 draw ``argmax(gumbel + log p)``. A backend's ``log``
rounds differently from another's (the TPU's by up to some 1e-4 nat,
PERF.md), so where the two best candidates lie closer than
``BIN_MARGIN`` or ``SAMPLE_MARGIN`` the estimator records a tie and
takes the candidate that ``picks`` names; :func:`best_branch` replays
every way of resolving the ties and keeps the one closest to what the
program answered.
"""

from __future__ import annotations

import numpy as np

M = 53
# widest gaps, in nats, that a backend's rounding can cross (PERF.md)
BIN_MARGIN = 2e-3
SAMPLE_MARGIN = 2e-3


def bins53() -> np.ndarray:
    """The paper's §4.3 grid of 53 candidate waits (seconds), float32."""
    return np.concatenate([
        np.arange(10.0, 100.0, 10.0), np.arange(100.0, 1000.0, 25.0),
        [1e3, 2e3, 4e3, 7e3], [1e4, 2e4, 5e4], [1e5]]).astype(np.float32)


BINS = bins53()
_LOG_BINS = np.log(BINS.astype(np.float64))


def nearest_bins(wait: float, margin: float = 0.0) -> list[int]:
    """Eq. (3): the bin closest to the observed wait in log space, then
    any other within ``margin`` nats of being as close."""
    w = max(float(np.float32(wait)), 1.0)
    d = np.abs(_LOG_BINS - np.log(w))
    best = int(np.argmin(d))
    return [best] + [int(i) for i in np.flatnonzero(d <= d[best] + margin)
                     if i != best]


def best_branch(run, score, cap: int = 64):
    """Replay along every way of resolving the ties, at most ``cap``
    times: ``run(picks)`` returns a result whose ``ties`` lists the
    number of candidates at each tie it met, ``score(result)`` how far
    it lies from the program. Returns the closest ``(result, picks)``."""
    best, stack, n = None, [()], 0
    while stack and n < cap:
        picks = stack.pop()
        r = run(picks)
        n += 1
        s = score(r)
        if best is None or s < best[0]:
            best = (s, r, picks)
        for pos in range(len(picks), len(r.ties)):
            for alt in range(1, r.ties[pos]):
                stack.append(picks + (0,) * (pos - len(picks)) + (alt,))
    return best[1], best[2]


class Alg1:
    """One estimator. ``draw(key) -> (key, gumbel[M])`` supplies the PRNG
    stream: the program samples Algorithm 1's line-4 action as
    ``argmax(gumbel(sub) + log p)`` after ``key, sub = split(key)``."""

    _salt = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, key, picks: tuple = ()) -> None:
        self.key = key
        self.picks = picks
        self.ties: list[int] = []            # candidates at each tie met
        self.C = np.zeros(M, np.int64)       # nats subtracted from log p
        self.R = np.zeros(M, np.int64)       # current round's losses
        self.hist = np.zeros(M, np.uint64)   # hash of each bin's losses
        self.events = 0

    def log_p(self) -> np.ndarray:
        x = -self.C.astype(np.float64)
        mx = x.max()
        return x - (mx + np.log(np.exp(x - mx).sum()))

    def _lose(self, amount: np.ndarray) -> None:
        self.events += 1
        hit = amount > 0
        with np.errstate(over="ignore"):
            self.hist[hit] = (self.hist[hit] * self._salt
                              + np.uint64(self.events) * np.uint64(977)
                              + amount[hit].astype(np.uint64))
        self.C += amount

    def _choose(self, cands: list[int]) -> int:
        if len(cands) == 1:
            return cands[0]
        k = len(self.ties)
        self.ties.append(len(cands))
        return cands[self.picks[k] if k < len(self.picks) else 0]

    def learn(self, wait: float, gumbel) -> None:
        """The tuned update at an observed wait (``gumbel``: the (M,)
        float32 Gumbel noise of this update's draw)."""
        best = self._choose(nearest_bins(wait, BIN_MARGIN))
        lv = np.ones(M, np.int64)
        lv[best] = 0
        v = np.asarray(gumbel, np.float32) + self.log_p().astype(np.float32)
        a = int(np.argmax(v))
        near = np.flatnonzero(v >= v[a] - np.float32(SAMPLE_MARGIN))
        a = self._choose([a] + [int(i) for i in near if i != a])
        self.R[a] += lv[a]
        if self.R.max() > 1:
            self._lose(self.R.copy())
            self.R[:] = 0
        self._lose(lv)

    def map_choices(self) -> list[int]:
        """Every bin index a sound float argmax of ``log p`` can return."""
        tied = np.flatnonzero(self.C == self.C.min())
        seen: dict[int, int] = {}
        for i in tied:
            seen.setdefault(int(self.hist[i]), int(i))
        return sorted(seen.values())

    def expected_and_entropy(self, dtype=np.float64) -> tuple[float, float]:
        """Posterior mean wait ⟨p, θ⟩ and Shannon entropy, in ``dtype``."""
        lp = self.log_p().astype(dtype)
        p = np.exp(lp).astype(dtype)
        return (float((p * BINS.astype(dtype)).sum(dtype=dtype)),
                float(-(p * lp).sum(dtype=dtype)))
