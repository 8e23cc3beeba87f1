"""repro.xsim: cross-validation vs QueueSim + scheduling invariants.

The cross-validation tests snapshot a live event-driven QueueSim into an
xsim job table and run both engines from the identical machine state —
waits and makespans must agree (exactly, for these deterministic
no-new-arrival scenarios; the assertions allow a small tolerance for the
bounded-backfill approximation). Both engines now learn *within* the
run: the ASA/ASA-Naive differential tests seed identical Algorithm-1
states on both sides and require the sampled prediction sequences to
match action-for-action through the whole scenario.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import asa
from repro.core.bins import make_bins
from repro.core.losses import zero_one
from repro.core.regret import empirical_regret, theorem1_bound
from repro.sched.centers import CenterProfile
from repro.sched.queue_sim import QueueSim
from repro.sched.strategies import (ASAEstimator, pilot_waste_cs, run_asa,
                                    run_bigjob, run_per_stage, run_pilot)
from repro.sched.workflows import BLAST, MONTAGE, STATISTICS
from repro.xsim import backfill, compare, events, policies
from repro.xsim import state as X
from repro.xsim.grid import (XSimConfig, make_grid, run_grid, stage_waits,
                             warm_fleet)
from repro.xsim.state import add_job, empty_table, freeze

TINY = CenterProfile(
    name="tiny", nodes=8, cores_per_node=4,
    bg_arrival_rate=1 / 200.0, bg_cores_mean=1.5, bg_cores_sigma=0.8,
    bg_duration_mean_s=7.0, bg_duration_sigma=0.8, bg_initial_backlog=12,
    bg_burst_mean=1.0, scales=(8,))

REL_TOL = 0.02  # bounded-backfill divergence allowance


def _mirrored(seed):
    """A warmed QueueSim (no further arrivals) + its xsim snapshot."""
    sim = QueueSim(TINY, seed=seed, bg_horizon=0.0)
    sim.run_until(600.0)
    table, row = compare.scenario_from_queue_sim(sim, max_jobs=64)
    return sim, table, row


def _close(a, b):
    assert a == pytest.approx(b, rel=REL_TOL, abs=5.0), (a, b)


# ------------------------------------------------------- cross-validation
@pytest.mark.parametrize("wf", [BLAST, STATISTICS])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bigjob_matches_queue_sim(wf, seed):
    sim, table, row = _mirrored(seed)   # snapshot BEFORE the ref run
    free = compare.queue_sim_free_cores(sim)
    ref = run_bigjob(sim, wf, 8, "tiny")

    policies.add_workflow(table, row, wf, 8, X.BIGJOB, t0=600.0)
    st = freeze(table, total_cores=TINY.total_cores, free_cores=free,
                now=600.0, policy=X.BIGJOB, t0=600.0)
    fin = events.simulate(st, n_steps=160)
    m = compare.metrics(fin)
    _close(float(m["twt_s"]), ref.twt_s)
    _close(float(m["makespan_s"]), ref.makespan_s)
    _close(float(m["core_hours"]), ref.core_hours)


@pytest.mark.parametrize("wf", [BLAST, STATISTICS, MONTAGE])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_per_stage_matches_queue_sim(wf, seed):
    sim, table, row = _mirrored(seed)   # snapshot BEFORE the ref run
    free = compare.queue_sim_free_cores(sim)
    ref = run_per_stage(sim, wf, 8, "tiny")

    policies.add_workflow(table, row, wf, 8, X.PER_STAGE, t0=600.0)
    st = freeze(table, total_cores=TINY.total_cores, free_cores=free,
                now=600.0, policy=X.PER_STAGE, t0=600.0)
    fin = events.simulate(st, n_steps=220)
    m = compare.metrics(fin)
    _close(float(m["twt_s"]), ref.twt_s)
    _close(float(m["makespan_s"]), ref.makespan_s)
    # utilization sanity on the shared background
    assert 0.0 < float(m["utilization"]) <= 1.0


@pytest.mark.parametrize("wf", [STATISTICS, MONTAGE])
@pytest.mark.parametrize("seed", [0, 2, 3])
@pytest.mark.parametrize("use_deps", [True, False])
def test_asa_matches_queue_sim(wf, seed, use_deps):
    """ASA (and §4.5 ASA-Naive) differential cross-validation.

    Both engines start from the *identical* machine snapshot AND the
    identical Algorithm-1 estimator state; both learn within the run.
    Perceived waits, makespans, overhead hours, miss counts and the full
    sampled prediction sequence must agree — the estimator's PRNG is
    consumed call-for-call in the same order on both sides.
    """
    sim, table, row = _mirrored(seed)   # snapshot BEFORE the ref run
    free = compare.queue_sim_free_cores(sim)
    ref = run_asa(sim, wf, 8, "tiny", ASAEstimator(seed=seed + 17),
                  use_dependencies=use_deps)

    pol = X.ASA if use_deps else X.ASA_NAIVE
    policies.add_workflow(table, row, wf, 8, pol, t0=600.0)
    st = freeze(table, total_cores=TINY.total_cores, free_cores=free,
                now=600.0, policy=pol, t0=600.0,
                est=asa.init(53, jax.random.PRNGKey(seed + 17)))
    fin = events.simulate(st, n_steps=300)
    m = compare.metrics(fin)
    _close(float(m["twt_s"]), ref.twt_s)
    _close(float(m["makespan_s"]), ref.makespan_s)
    assert float(m["oh_hours"]) == pytest.approx(ref.oh_hours, abs=1e-3)
    assert int(m["misses"]) == ref.misses
    if use_deps:
        assert float(m["oh_hours"]) == 0.0  # dependency-ASA never idles
    # live-sampled cascade estimates match the event-driven sequence
    # exactly (stage 0's a_0 is not recorded in RunMetrics.pred_waits)
    preds = np.asarray(fin.pred_wait)[np.asarray(fin.is_wf)]
    np.testing.assert_allclose(preds[1:len(ref.pred_waits) + 1],
                               ref.pred_waits)
    # within-run learning really ran inside the scan: one tuned update
    # (2 estimator events) per settled stage start
    assert int(fin.est.t) >= 2 * len(wf.stages)


@pytest.mark.parametrize("wf", [BLAST, STATISTICS])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pilot_matches_queue_sim(wf, seed):
    """Pilot-job differential: one peak-width allocation whose walltime
    adds the pilot bootstrap + per-stage dispatch latency on top of the
    serialized stage work. Both engines model the identical single-job
    shape, so the match is exact (same machine snapshot, no divergence
    sources) — the tolerance is the shared ``_close`` formality."""
    sim, table, row = _mirrored(seed)   # snapshot BEFORE the ref run
    free = compare.queue_sim_free_cores(sim)
    ref = run_pilot(sim, wf, 8, "tiny")

    policies.add_workflow(table, row, wf, 8, X.PILOT, t0=600.0)
    st = freeze(table, total_cores=TINY.total_cores, free_cores=free,
                now=600.0, policy=X.PILOT, t0=600.0,
                pilot_waste_cs=pilot_waste_cs(wf, 8))
    fin = events.simulate(st, n_steps=160)
    m = compare.metrics(fin)
    _close(float(m["twt_s"]), ref.twt_s)
    _close(float(m["makespan_s"]), ref.makespan_s)
    _close(float(m["core_hours"]), ref.core_hours)
    # the over-allocation waste is charged as OH once the pilot runs
    assert float(m["oh_hours"]) == pytest.approx(ref.oh_hours, rel=1e-5)
    assert float(m["oh_hours"]) > 0.0
    assert int(m["wf_done"]) == int(m["wf_total"]) == 1


def test_naive_cancel_resubmit_exercised():
    """Across the differential seeds the naive path must actually cancel:
    at least one mirrored scenario takes the CANCELLED→resubmit edge and
    charges cancel-latency OH (montage seed 2 takes seven misses)."""
    total_miss, total_oh = 0, 0.0
    for seed in (0, 2, 3):
        sim, table, row = _mirrored(seed)
        free = compare.queue_sim_free_cores(sim)
        policies.add_workflow(table, row, MONTAGE, 8, X.ASA_NAIVE, t0=600.0)
        st = freeze(table, total_cores=TINY.total_cores, free_cores=free,
                    now=600.0, policy=X.ASA_NAIVE, t0=600.0,
                    est=asa.init(53, jax.random.PRNGKey(seed + 17)))
        fin = events.simulate(st, n_steps=300)
        m = compare.metrics(fin)
        total_miss += int(m["misses"])
        total_oh += float(m["oh_hours"])
        assert int(m["wf_done"]) == int(m["wf_total"])  # resubmits finish
    assert total_miss >= 3
    assert total_oh > 0.0


# ------------------------------------------------------------ invariants
def _bare(total=100.0, free=100.0, max_jobs=16, policy=X.BIGJOB):
    return empty_table(max_jobs), dict(total_cores=total, free_cores=free,
                                       policy=policy)


def test_never_over_allocates():
    """min_free stays ≥ 0 across a busy random scenario sweep."""
    cfg = XSimConfig(n_warm=16, n_backlog=12, n_arrivals=16, max_stages=9,
                     t0=1800.0)
    grid = make_grid(cfg, n_seeds=2, shrink=1 / 128.0,
                     workflows=("montage",))
    final, m = run_grid(grid)
    assert float(jnp.min(final.min_free)) >= 0.0
    # conservation at the end of the sweep
    running = np.asarray(final.status) == X.RUNNING
    used = np.sum(np.where(running, np.asarray(final.cores), 0.0), axis=1)
    np.testing.assert_allclose(used + np.asarray(final.free),
                               np.asarray(final.total), rtol=1e-5)


def test_fcfs_order_respected():
    """Equal-width jobs start in submission order."""
    t, kw = _bare()
    for i, sub in enumerate((0.0, 10.0, 20.0, 30.0)):
        add_job(t, i, cores=60, duration=100.0, submit=sub, status=X.PENDING)
    st = freeze(t, **kw)
    fin = events.simulate(st, n_steps=30)
    starts = np.asarray(fin.start[:4])
    assert np.all(np.diff(starts) > 0)  # 60-core jobs serialize, in order


def test_backfill_fills_without_delaying_head():
    """A short narrow job backfills ahead of a blocked wide head job,
    and the head still starts exactly at its reservation (shadow) time."""
    t, kw = _bare(free=40.0)
    # 60 cores busy until t=1000
    add_job(t, 0, cores=60, duration=1000.0, submit=0.0, status=X.RUNNING,
            start=0.0, end=1000.0)
    t["start"][0] = 0.0
    t["end"][0] = 1000.0
    # head: wants 80 cores -> must wait for t=1000 (shadow)
    add_job(t, 1, cores=80, duration=500.0, submit=10.0, status=X.PENDING)
    # backfill candidate: 20 cores, drains before the shadow
    add_job(t, 2, cores=20, duration=400.0, submit=20.0, status=X.PENDING)
    # NOT backfillable: 20 cores but too long (would delay nothing core-wise
    # but exceeds the shadow window and the spare at shadow is 100-80=20...
    # cores 30 > spare 20 and duration crosses the shadow)
    add_job(t, 3, cores=30, duration=5000.0, submit=30.0, status=X.PENDING)
    st = freeze(t, **kw)
    fin = events.simulate(st, n_steps=30)
    start = np.asarray(fin.start)
    assert start[2] == 20.0          # backfilled immediately at submit
    assert start[1] == 1000.0        # head starts exactly at shadow time
    assert start[3] >= 1000.0        # long job could not jump the head


def test_backfill_in_spare_cores_of_reservation():
    """A long narrow job may still backfill if it fits the reservation's
    spare cores (EASY 'extra' rule)."""
    t, kw = _bare(free=40.0)
    add_job(t, 0, cores=60, duration=1000.0, submit=0.0, status=X.RUNNING,
            start=0.0, end=1000.0)
    t["start"][0] = 0.0
    t["end"][0] = 1000.0
    add_job(t, 1, cores=80, duration=500.0, submit=10.0, status=X.PENDING)
    # 15 cores <= extra (100-80=20): backfills despite 5000s duration
    add_job(t, 2, cores=15, duration=5000.0, submit=20.0, status=X.PENDING)
    st = freeze(t, **kw)
    fin = events.simulate(st, n_steps=30)
    assert float(fin.start[2]) == 20.0
    assert float(fin.start[1]) == 1000.0


def test_dependency_blocks_start():
    t, kw = _bare()
    add_job(t, 0, cores=10, duration=500.0, submit=0.0, status=X.PENDING)
    add_job(t, 1, cores=10, duration=100.0, submit=0.0, status=X.PENDING,
            start_dep=0)
    st = freeze(t, **kw)
    fin = events.simulate(st, n_steps=30)
    assert float(fin.start[1]) >= float(fin.end[0]) == 500.0


@pytest.mark.parametrize("B,N", [(3, 128), (11, 53)])
def test_pallas_reservation_matches_reference(B, N):
    """Sorted jnp path and sorted Pallas kernel == the O(n²) reference,
    exactly — including duplicated end times (tie runs). (11, 53) pads the
    batch to two ``ROW_TILE`` blocks at a width that is no lane multiple."""
    rng = np.random.default_rng(3)
    ends = jnp.asarray(rng.uniform(0, 1e4, (B, N)), jnp.float32)
    ends = ends.at[:, ::4].set(5000.0)          # force ties
    cores = jnp.asarray(rng.integers(1, 50, (B, N)), jnp.float32)
    running = jnp.asarray(rng.random((B, N)) < 0.5)
    ref = jax.vmap(backfill._freed_math)(ends, cores, running)
    srt = jax.vmap(backfill._freed_sorted)(ends, cores, running)
    ker = backfill.freed_matrix(ends, cores, running, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(srt))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_chunked_simulate_respects_step_budget():
    """Chunked and unchunked simulate are bitwise identical in BOTH
    regimes: drained (extra chunk steps are no-ops) and truncated (the
    while_loop runs ⌊n_steps/chunk⌋ chunks plus a static remainder scan,
    never granting more than exactly ``n_steps`` steps — a budget that
    is not a chunk multiple must not be rounded up)."""
    t, kw = _bare()
    for i, sub in enumerate((0.0, 500.0, 1000.0, 1500.0, 2000.0)):
        add_job(t, i, cores=60, duration=100.0, submit=sub,
                status=X.PENDING)
    st = freeze(t, **kw)
    # truncation regime: 3 steps of budget, chunk default 8, events left
    a = events.simulate(st, n_steps=3, chunk_steps=0)
    b = events.simulate(st, n_steps=3)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(b.steps) == 3
    # drained regime: every chunk size reproduces the static scan
    c = events.simulate(st, n_steps=40, chunk_steps=0)
    for k in (1, 8, 64):
        d = events.simulate(st, n_steps=40, chunk_steps=k)
        for x, y in zip(jax.tree.leaves(c), jax.tree.leaves(d)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_freed_mode_ref_n2_end_to_end():
    """The sorted default and the retained O(n²) reference drive bitwise
    identical simulations (the reservation rework is numerically
    invisible on the integer-core tables the engine uses)."""
    t, kw = _bare()
    policies.add_workflow(t, 0, MONTAGE, 28, X.PER_STAGE, t0=0.0)
    st = freeze(t, policy=X.PER_STAGE, total_cores=100.0, free_cores=100.0)
    a = events.simulate(st, n_steps=48)
    b = events.simulate(st, n_steps=48, freed_mode="ref_n2")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError, match="freed mode"):
        events.simulate(st, n_steps=8, freed_mode="bogus")


_HLO_OPCODE = re.compile(r"=\s*(?:\(.*?\)|\S+)\s+([a-z][a-z0-9-]*)\(")


def test_default_reservation_compiles_without_gather_or_loop():
    """On the default path the reservation is one sort, a cumsum and
    reductions: no instruction the compiler keeps under ``xsim.reserve``
    is a ``gather`` (an index read over the rows) or a ``while`` (the
    binary search of ``searchsorted``)."""
    t, kw = _bare()
    for i in range(6):
        add_job(t, i, cores=30, duration=100.0 * (i + 1), submit=0.0,
                status=X.QUEUED)
    st = freeze(t, **kw)
    hlo = jax.jit(lambda s: backfill.schedule_pass(s, freed_mode="ref")
                  ).lower(st).compile().as_text()
    ops = {m.group(1) for line in hlo.splitlines() if "xsim.reserve" in line
           for m in [_HLO_OPCODE.search(line)] if m}
    assert "sort" in ops
    assert not ops & {"gather", "while"}, sorted(ops)


def test_pallas_freed_mode_end_to_end():
    t, kw = _bare()
    policies.add_workflow(t, 0, STATISTICS, 28, X.PER_STAGE, t0=0.0)
    st = freeze(t, policy=X.PER_STAGE, total_cores=100.0, free_cores=100.0)
    a = events.simulate(st, n_steps=40)
    b = events.simulate(st, n_steps=40, freed_mode="interpret")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- fleet sweep + ordering
def test_vmapped_sweep_and_table1_ordering():
    """One jitted vmapped program over the full grid (all five queue
    policies, learning within each scan) reproduces the paper's
    qualitative Table-1 ordering:
      CH(asa) == CH(per_stage) < CH(bigjob),
      TWT and makespan: ASA no worse than Per-Stage, scenario by scenario,
      TWT: ASA no worse than BigJob in most scenarios, better in some,
    the §4.5 Naive/Dependency trade-off (ASA-Naive pays OH > 0 and loses
    perceived waiting time to dependency-ASA), and the pilot-job
    trade-off: a pilot queues ONCE at peak width (so its queue wait is
    BigJob's, within reach of Per-Stage's summed stage waits) but pays
    BigJob-like packing waste plus bootstrap/dispatch overhead —
    CH(pilot) == CH(asa) + OH(pilot), mirroring ASA-Naive's identity.

    The wait claims are paired (same machine, workflow and seed) over 8
    seeds, not means of 2. A mean over few seeds is decided by the odd
    scenario where the live estimator's MAP drops in mid-cascade: the
    stale over-estimate stays in the chained expected end E_y, and the
    successor goes in late (the E_y − a_{y+1} rule of
    ``strategies.run_asa``). Measured over 4, 8, 16 and 32 seeds under
    both of JAX's threefry streams, with this code, ASA's mean TWT ran
    0.87-1.21× BigJob's and 0.93-1.10× Per-Stage's, so neither mean
    ordering is a property of this miniature config and neither is
    asserted. The paired shares held in every one of those runs: ASA no
    worse than Per-Stage in ≥ 96.5% of pairs (TWT and makespan), than
    ASA-Naive in ≥ 93.8%, and than BigJob in 76.4-87.7% (79.2% for these
    8 seeds under the default stream), strictly better than BigJob in
    10-24%; the other pairs are equal."""
    cfg = XSimConfig(n_warm=24, n_backlog=16, n_arrivals=24, max_stages=9,
                     t0=3600.0)
    grid = make_grid(cfg, n_seeds=8, shrink=1 / 64.0,
                     policy_ids=(0, 1, 2, 3, 5))
    fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    fleet = warm_fleet(fleet, grid, rounds=3)
    final, m = run_grid(grid, fleet, pred_seed=7)
    m = {k: np.asarray(v) for k, v in m.items()}

    # every scenario finished inside the step budget
    assert np.all(m["wf_done"] == m["wf_total"])
    assert np.all(np.isfinite(m["makespan_s"]))

    by = {}
    for i, lab in enumerate(grid.labels):
        by.setdefault(lab["strategy"], []).append(i)
    mean = {s: {k: float(np.mean(m[k][idx])) for k in
                ("twt_s", "makespan_s", "core_hours", "oh_hours")}
            for s, idx in by.items()}
    # make_grid orders cells identically within every policy, so equal
    # positions in these index lists are the same (machine, wf, seed)
    paired = {s: {k: m[k][idx] for k in ("twt_s", "makespan_s")}
              for s, idx in by.items()}

    def share_no_worse(a, b, key):
        return float(np.mean(paired[a][key] <= paired[b][key] + 1e-3))

    # CH(asa) == CH(per_stage) < CH(bigjob)  (paper: BigJob +53% CH)
    assert mean["asa"]["core_hours"] == pytest.approx(
        mean["per_stage"]["core_hours"], rel=1e-6)
    assert mean["bigjob"]["core_hours"] > 1.2 * mean["asa"]["core_hours"]
    # ASA hides stage waits behind execution: its perceived wait and its
    # makespan are Per-Stage's or better in nearly every paired scenario,
    # and strictly better in some
    assert share_no_worse("asa", "per_stage", "twt_s") >= 0.95
    assert share_no_worse("asa", "per_stage", "makespan_s") >= 0.95
    assert np.any(paired["asa"]["twt_s"] < paired["per_stage"]["twt_s"])
    # ...and its perceived wait is BigJob's or better in most paired
    # scenarios, strictly better in some (paper: BigJob's TWT is higher)
    assert share_no_worse("asa", "bigjob", "twt_s") >= 0.75
    assert np.any(paired["asa"]["twt_s"] < paired["bigjob"]["twt_s"])
    # §4.5 trade-off: without dependency support ASA-Naive mispredicts
    # into idle/cancel overhead and a worse perceived wait than ASA
    assert mean["asa_naive"]["oh_hours"] > 0.0
    assert share_no_worse("asa", "asa_naive", "twt_s") >= 0.9
    assert mean["asa_naive"]["core_hours"] == pytest.approx(
        mean["asa"]["core_hours"] + mean["asa_naive"]["oh_hours"], rel=1e-5)
    # pilot queue wait: one peak-width submission at t0 — identical queue
    # position to BigJob's (same width, same instant), and within a small
    # slack of Per-Stage's summed narrow-stage waits
    assert mean["bigjob"]["twt_s"] <= mean["pilot"]["twt_s"] + 1e-3
    assert mean["pilot"]["twt_s"] <= 1.1 * mean["per_stage"]["twt_s"]
    # ...but the pilot pays for it: bootstrap + dispatch stretch the
    # makespan past BigJob's, the over-allocation is charged as OH, and
    # the core-hours identity mirrors ASA-Naive's
    assert mean["pilot"]["makespan_s"] > mean["bigjob"]["makespan_s"]
    assert mean["pilot"]["oh_hours"] > 0.0
    assert mean["pilot"]["core_hours"] == pytest.approx(
        mean["asa"]["core_hours"] + mean["pilot"]["oh_hours"], rel=1e-5)
    assert mean["pilot"]["core_hours"] > mean["bigjob"]["core_hours"]
    # the other strategies never accrue OH
    for strat in ("bigjob", "per_stage", "asa"):
        assert mean[strat]["oh_hours"] == 0.0


def test_within_run_learning_regret_convergence():
    """Theorem-1 regression for in-scan learning (paper Appendix A).

    A 3-round warm-started sweep observes a per-geometry wait sequence;
    on that sequence the adaptive tuned estimator must (a) actually have
    learned inside the scan (estimator case-counts advanced for ASA
    scenarios only), (b) keep empirical regret under the Theorem-1 bound,
    and (c) be no worse than the frozen-MAP baseline — the prediction
    rule the engine used before within-run learning landed.
    """
    cfg = XSimConfig(n_warm=16, n_backlog=12, n_arrivals=16, max_stages=9,
                     t0=1800.0)
    grid = make_grid(cfg, n_seeds=4, shrink=1 / 64.0,
                     workflows=("statistics",), policy_ids=(1, 2))
    fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    fleet = warm_fleet(fleet, grid, rounds=3)
    final, m = run_grid(grid, fleet)

    # (a) the scan carried the estimator: only ASA scenarios learned
    init_t = np.asarray(fleet.t)[grid.geo_idx]
    est_t = np.asarray(final.est.t)
    strat = np.array([lab["strategy"] for lab in grid.labels])
    is_asa = strat == "asa"
    assert np.all(est_t[is_asa] > init_t[is_asa])
    assert np.all(est_t[~is_asa] == init_t[~is_asa])

    # (b) + (c): replay the full 3-round observation sequence per geometry.
    # The warm rounds + final sweep are the sequence the learner actually
    # saw; the "frozen" baseline predicts with the cold initial MAP for
    # the whole campaign — exactly what predictions looked like before
    # within-run learning landed, on a fresh fleet.
    n_geo = int(grid.geo_idx.max()) + 1
    seqs: list[list[float]] = [[] for _ in range(n_geo)]
    replay_fleet = policies.init_fleet(n_geo)
    for r in range(3):
        rf, _ = run_grid(grid, replay_fleet, pred_seed=100 + r)
        w_r, v_r = stage_waits(rf, cfg)
        for g in range(n_geo):
            sel = (grid.geo_idx == g) & is_asa
            seqs[g].extend(w_r[sel][v_r[sel]].tolist())
        W = np.zeros((n_geo, 8), np.float32)
        V = np.zeros((n_geo, 8), bool)
        for g in range(n_geo):
            w = w_r[(grid.geo_idx == g) & is_asa, 0]
            w = w[v_r[(grid.geo_idx == g) & is_asa, 0]][:8]
            W[g, :len(w)] = w
            V[g, :len(w)] = True
        replay_fleet = policies.update_fleet(replay_fleet, jnp.asarray(W),
                                             jnp.asarray(V))
    bins = jnp.asarray(make_bins(53), jnp.float32)
    cold = asa.init(53, jax.random.PRNGKey(0))
    a_frozen = int(np.argmax(np.asarray(cold.log_p)))  # cold MAP, fixed
    g_one = jnp.float32(1.0)
    total_adaptive = total_frozen = 0.0
    for g in range(n_geo):
        ws = seqs[g]
        if not ws:
            continue
        L = np.stack([np.asarray(zero_one(bins, jnp.float32(max(w, 1.0))))
                      for w in ws])
        state = cold
        eta0 = int(state.rounds)
        chosen = []
        for lv in L:
            # live-MAP decision (the fleet-sweep prediction rule), tuned
            # §4.5 learning from the observed wait — as the scan hooks do
            chosen.append(lv[int(np.argmax(np.asarray(state.log_p)))])
            state, _ = asa.step(state, jnp.asarray(lv), g_one,
                                policy="tuned")
        r_adaptive = empirical_regret(np.asarray(chosen), L)
        assert r_adaptive <= theorem1_bound(
            len(chosen), 53, int(state.rounds) - eta0)
        total_adaptive += r_adaptive
        total_frozen += empirical_regret(L[:, a_frozen], L)
    # learning while running beats the frozen cold-MAP predictor
    assert total_adaptive <= total_frozen


def test_stage_waits_and_fleet_learning():
    """warm_fleet moves each geometry's MAP estimate toward its observed
    first-stage wait decade (the §4.3 cross-run persistence loop)."""
    cfg = XSimConfig(n_warm=16, n_backlog=12, n_arrivals=16, max_stages=9,
                     t0=1800.0)
    grid = make_grid(cfg, n_seeds=2, shrink=1 / 64.0,
                     workflows=("statistics",))
    fleet0 = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    fleet = warm_fleet(fleet0, grid, rounds=2)
    # distributions moved away from uniform
    assert not np.allclose(np.asarray(fleet.log_p), np.asarray(fleet0.log_p))
    final, _ = run_grid(grid, fleet)
    waits, valid = stage_waits(final, cfg)
    assert waits.shape == (grid.n, cfg.max_stages)
    assert valid.any()
