"""Property-based invariants of the xsim slotted engine.

Hypothesis drives randomized small scenarios (random machine fill, random
backlog/arrival mixes, every policy including ASA-Naive) through the
event scan step by step, asserting the invariants the engine's masked
array writes must never break:

* core conservation — Σ cores(RUNNING) + free == total at every step,
  and used cores never exceed capacity (min_free ≥ 0);
* status-ladder monotonicity — INVALID→PENDING→QUEUED→RUNNING→DONE only
  moves forward, except the two explicit ASA-Naive cancel edges
  (RUNNING→CANCELLED at a mispredicted start, CANCELLED→QUEUED at the
  resubmission);
* causality — start ≥ submit for every started job;
* estimator sanity — the in-scan ASA state stays a normalized
  distribution (finite log_p, logsumexp ≈ 0).

CI installs real ``hypothesis``; minimal environments fall back to the
deterministic replay stub in conftest.py (same API surface).
"""

from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.bins import make_bins
from repro.sched.workflows import BLAST, MONTAGE, STATISTICS
from repro.xsim import backfill, events, policies
from repro.xsim import state as X
from repro.xsim.grid import XSimConfig, make_grid, run_grid
from repro.xsim.state import add_job, empty_table, freeze

MAX_JOBS = 24
TOTAL = 64.0
N_STEPS = 70
BINS = jnp.asarray(make_bins(53), jnp.float32)

# one jitted step for all examples (fixed shapes -> single compile)
_step = jax.jit(lambda s: events.sim_step(s, BINS))

POLICIES = (X.BIGJOB, X.PER_STAGE, X.ASA, X.ASA_NAIVE)
WORKFLOWS = (STATISTICS, BLAST, MONTAGE)

# forward edges of the ladder + the two explicit naive cancel edges
_EDGES = {
    (X.PENDING, X.QUEUED), (X.QUEUED, X.RUNNING), (X.RUNNING, X.DONE),
    (X.RUNNING, X.CANCELLED),   # naive miss: cancel at start instant
    (X.CANCELLED, X.QUEUED),    # naive resubmission re-enters the queue
}
# one sim_step can compose several edges at the same instant, but only in
# the step's fixed order (releases → admissions → scheduling pass → cancel
# hook): admit+start (P→R), admit+start+cancel (P/Q→C), resubmit+start
# (C→R). A completion can never share a step with the same row's start
# (durations are positive), so *→DONE composites stay impossible.
_ALLOWED = _EDGES | {
    (X.PENDING, X.RUNNING), (X.PENDING, X.CANCELLED),
    (X.QUEUED, X.CANCELLED), (X.CANCELLED, X.RUNNING),
}


def _random_scenario(seed: int, policy_i: int, fill: float):
    """A small random machine + backlog + one workflow, host-built."""
    rng = np.random.default_rng(seed)
    policy = POLICIES[policy_i % len(POLICIES)]
    wf = WORKFLOWS[seed % len(WORKFLOWS)]
    t = empty_table(MAX_JOBS)
    row = 0
    used = 0.0
    for _ in range(int(rng.integers(0, 7))):          # warm-start running
        c = float(rng.integers(1, 24))
        if used + c > fill * TOTAL:
            break
        d = float(rng.uniform(50.0, 5000.0))
        add_job(t, row, cores=c, duration=d, submit=0.0, status=X.RUNNING,
                start=0.0, end=float(rng.uniform(1.0, d)))
        used += c
        row += 1
    for _ in range(int(rng.integers(0, 6))):          # queued backlog
        add_job(t, row, cores=float(rng.integers(1, 32)),
                duration=float(rng.uniform(50.0, 5000.0)), submit=0.0,
                status=X.QUEUED)
        row += 1
    for _ in range(int(rng.integers(0, 5))):          # future arrivals
        add_job(t, row, cores=float(rng.integers(1, 32)),
                duration=float(rng.uniform(50.0, 5000.0)),
                submit=float(rng.uniform(1.0, 4000.0)), status=X.PENDING)
        row += 1
    t0 = float(rng.uniform(0.0, 2000.0))
    policies.add_workflow(t, row, wf, 8, policy, t0=t0)
    mode = "sample" if seed % 2 else "greedy"
    return freeze(t, total_cores=TOTAL, free_cores=TOTAL - used,
                  policy=policy, t0=t0, est_seed=seed, pred_mode=mode)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 3), st.floats(0.1, 0.95))
def test_invariants_hold_at_every_step(seed, policy_i, fill):
    s = _random_scenario(seed, policy_i, fill)
    prev_status = np.asarray(s.status)
    for _ in range(N_STEPS):
        s = _step(s)
        status = np.asarray(s.status)
        cores = np.asarray(s.cores)
        free = float(s.free)
        # --- core conservation, never over capacity -------------------
        used = float(np.sum(np.where(status == X.RUNNING, cores, 0.0)))
        assert used + free == pytest.approx(float(s.total), abs=1e-3)
        assert free >= -1e-3
        assert float(s.min_free) >= -1e-3
        # --- status ladder only moves along allowed edges -------------
        for a, b in zip(prev_status, status):
            if a != b:
                assert (int(a), int(b)) in _ALLOWED, (int(a), int(b))
        prev_status = status
        # --- causality ------------------------------------------------
        start = np.asarray(s.start)
        submit = np.asarray(s.submit)
        started = np.isfinite(start)
        assert np.all(start[started] >= submit[started] - 1e-3)
    # --- the in-scan estimator is still a normalized distribution -----
    log_p = np.asarray(s.est.log_p)
    assert np.all(np.isfinite(log_p))
    assert abs(float(jax.nn.logsumexp(s.est.log_p))) < 1e-3


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64),
       st.booleans())
def test_sorted_freed_matches_n2_reference_exactly(seed, n, force_ties):
    """The O(n log n) sorted reservation == the O(n²) pairwise reference,
    bit for bit, on random integer-core job tables — end-time ties (the
    searchsorted side="right" case) and non-running rows included. Core
    counts are integer-valued in every grid, so both the sorted cumsum
    and the reference's row-order sum are exact integer arithmetic and
    the two formulations must agree EXACTLY, not approximately."""
    rng = np.random.default_rng(seed)
    if force_ties:
        # few distinct end times over many rows ⇒ guaranteed tie runs
        ends = rng.choice([60.0, 600.0, 600.0, 3600.0, 86400.0], size=n)
    else:
        ends = rng.uniform(0.0, 1e5, n)
    cores = rng.integers(1, 512, n).astype(np.float32)
    running = rng.random(n) < 0.7
    ref = backfill._freed_math(jnp.asarray(ends, jnp.float32),
                               jnp.asarray(cores), jnp.asarray(running))
    fast = backfill._freed_sorted(jnp.asarray(ends, jnp.float32),
                                  jnp.asarray(cores), jnp.asarray(running))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fast))


def _both_reservations(ends, cores, running, free, head_cores):
    """(shadow, extra) computed directly, and read from the O(n²)
    reference's freed vector."""
    direct = backfill.reservation(ends, cores, running, free, head_cores)
    via_vector = backfill.reservation(
        ends, cores, running, free, head_cores,
        freed=backfill._freed_math(ends, cores, running))
    return direct, via_vector


# head cases: any width, wider than the whole machine, just what the
# first end-time tie run frees, zero cores
_HEAD_ANY, _HEAD_NEVER, _HEAD_FIRST, _HEAD_ZERO = range(4)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64), st.booleans(),
       st.integers(0, 4), st.integers(0, 3))
def test_direct_reservation_matches_freed_vector_exactly(
        seed, n, force_ties, fill, head_case):
    """The default reservation, computed from one sort of (end, cores)
    with no freed vector, returns the same (shadow, extra) as reading
    the O(n²) reference's vector, bit for bit: tie runs, tables with no
    running row (``fill`` 0), a head that never fits (+inf, 0), one that
    fits at the first end, and a head of 0 cores included."""
    rng = np.random.default_rng(seed)
    if force_ties:
        ends = rng.choice([60.0, 600.0, 600.0, 3600.0, 86400.0], size=n)
    else:
        ends = rng.uniform(0.0, 1e5, n)
    ends = ends.astype(np.float32)
    cores = rng.integers(1, 512, n).astype(np.float32)
    running = rng.random(n) < fill / 4
    free = np.float32(rng.integers(0, 200))
    if head_case == _HEAD_NEVER:
        head = free + cores[running].sum() + 1
    elif head_case == _HEAD_FIRST and running.any():
        first = ends[running].min()
        head = free + cores[running & (ends == first)].sum()
    elif head_case == _HEAD_ZERO:
        head = 0.0
    else:
        head = rng.integers(0, free + cores[running].sum() + 2)
    direct, via_vector = _both_reservations(
        jnp.asarray(ends), jnp.asarray(cores), jnp.asarray(running),
        jnp.float32(free), jnp.float32(head))
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(via_vector))
    if head_case == _HEAD_NEVER:
        assert float(direct[0]) == np.inf and float(direct[1]) == 0.0
    if head_case == _HEAD_FIRST and running.any():
        assert float(direct[0]) == ends[running].min()


def test_direct_reservation_matches_at_the_sweep_width():
    """Vmapped over 9 scenarios of 2,313 rows (the UPPMAX cell's table):
    a full 9,720-core machine with a deep backlog, so most rows are
    queued, end times fall on a minute grid (long tie runs), and the
    heads range over none, one core, random widths and more than the
    whole machine. Direct and vector reservations agree bit for bit."""
    rng = np.random.default_rng(3_200_000_001)
    b, n, total = 9, 2313, 9720.0
    cores = rng.integers(1, 160, (b, n)).astype(np.float32)
    running = np.zeros((b, n), bool)
    for i in range(b):          # fill each machine, in a random order
        order = rng.permutation(n)
        fit = np.cumsum(cores[i, order]) <= total - 8 * i
        running[i, order[fit]] = True
    ends = (60.0 * rng.integers(1, 2880, (b, n))).astype(np.float32)
    free = total - np.where(running, cores, 0.0).sum(axis=1)
    head = rng.integers(1, int(total), b).astype(np.float32)
    head[:3] = (0.0, 1.0, total + 1.0)
    direct, via_vector = jax.jit(jax.vmap(_both_reservations))(
        jnp.asarray(ends), jnp.asarray(cores), jnp.asarray(running),
        jnp.asarray(free, jnp.float32), jnp.asarray(head))
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(via_vector))
    assert np.isinf(np.asarray(direct[0])[2])
    assert np.isfinite(np.asarray(direct[0])[3:]).all()


_GRID_CFG = XSimConfig(n_warm=8, n_backlog=6, n_arrivals=8, max_stages=9,
                       t0=1800.0)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_grid_sweep_invariants(seed):
    """Random full grids (all four policies) keep capacity + completion
    invariants through the vmapped sweep."""
    grid = make_grid(_GRID_CFG, n_seeds=1, shrink=1 / 128.0,
                     workflows=("statistics",), policy_ids=(0, 1, 2, 3),
                     seed=seed)
    final, m = run_grid(grid)
    assert float(jnp.min(final.min_free)) >= 0.0
    running = np.asarray(final.status) == X.RUNNING
    used = np.sum(np.where(running, np.asarray(final.cores), 0.0), axis=1)
    np.testing.assert_allclose(used + np.asarray(final.free),
                               np.asarray(final.total), rtol=1e-5)
    # every scenario's workflow finished inside the static step budget
    assert np.all(np.asarray(m["wf_done"]) == np.asarray(m["wf_total"]))
    # OH only ever accrues on the naive policy
    oh = np.asarray(m["oh_hours"])
    pol = np.asarray(m["policy"])
    assert np.all(oh[pol != X.ASA_NAIVE] == 0.0)
    assert np.all(oh >= 0.0)


def test_full_grid_drains_within_budget():
    """Every scenario of a full default ``make_grid`` sweep (all centers,
    scales, workflows and the naive cancel/resubmit policy included) must
    have ``next_event_time == +inf`` at budget end — i.e. the tightened
    ``n_steps`` formula (2·max_jobs + 2·max_stages + 16: the 6·max_stages
    cascade term absorbed by the in-step hook drain, the surviving slack
    covering worst-case cancel detours) silently truncates NOTHING. The
    per-scenario ``steps`` counter must also sit strictly below the
    budget for at least some scenarios (the event-bound signal the
    ``--profile`` record tracks) and never above it."""
    cfg = XSimConfig(n_warm=16, n_backlog=12, n_arrivals=16, max_stages=9,
                     t0=3600.0)
    grid = make_grid(cfg, n_seeds=2, shrink=1 / 64.0,
                     policy_ids=(0, 1, 2, 3))
    final, m = run_grid(grid)
    nxt = np.asarray(jax.jit(jax.vmap(events.next_event_time))(final))
    assert np.all(np.isinf(nxt)), (
        f"{int(np.sum(np.isfinite(nxt)))} scenarios still had events at "
        f"budget end (n_steps={cfg.n_steps})")
    assert np.all(np.asarray(m["wf_done"]) == np.asarray(m["wf_total"]))
    steps = np.asarray(final.steps)
    assert int(steps.max()) <= cfg.n_steps
    assert float(steps.mean()) < cfg.n_steps  # budget-bound no more
