"""Scenario-grid construction + fleet sweep runner.

A *grid cell* is (center × scale × workflow × policy); a *scenario* is a
cell plus a PRNG seed drawing its background workload. All cell
parameters are data (stacked arrays), so ``jax.vmap(build_scenario)``
materializes thousands of scenarios in one traced program and
``events.sweep`` runs them as one batched ``lax.scan`` — the fleet-scale
substrate the ROADMAP's "as many scenarios as you can imagine" asks for.

The background generator mirrors ``QueueSim``'s calibrated model
(Poisson bursts, log-normal widths/durations, warm-start residuals +
backlog) with two slotted-state approximations, documented in README.md:
burst sizes are drawn per arrival *group* up front, and the warm-start
fill stops at the capacity target instead of clipping the last job.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace
from repro.runtime.fault import FaultSchedule
from repro.sched.centers import CENTERS, CenterProfile
from repro.sched.strategies import PILOT_STARTUP_S, PILOT_TASK_LATENCY_S
from repro.sched.workflows import WORKFLOWS, Workflow
from repro.xsim import backfill, events, policies
from repro.xsim.state import (ASA_NAIVE, BIGJOB, INVALID, PENDING, PILOT,
                              POLICY_NAMES, QUEUED, RL, RL_FEATURES,
                              RUNNING, ScenarioState)


class XCenter(NamedTuple):
    """Center parameters as data (vmap-able across scenarios)."""

    total_cores: jax.Array
    bg_arrival_rate: jax.Array
    bg_cores_mean: jax.Array
    bg_cores_sigma: jax.Array
    bg_duration_mean_s: jax.Array
    bg_duration_sigma: jax.Array
    bg_backlog: jax.Array
    bg_burst_mean: jax.Array


def center_params(p: CenterProfile, shrink: float = 1.0) -> XCenter:
    """A (possibly miniaturized) center. ``shrink`` scales the machine,
    the backlog and the arrival rate together, preserving offered load —
    small grids simulate fast while keeping the congestion regime."""
    return XCenter(
        total_cores=jnp.float32(max(p.total_cores * shrink, 8.0)),
        bg_arrival_rate=jnp.float32(p.bg_arrival_rate * shrink),
        bg_cores_mean=jnp.float32(p.bg_cores_mean),
        bg_cores_sigma=jnp.float32(p.bg_cores_sigma),
        bg_duration_mean_s=jnp.float32(p.bg_duration_mean_s),
        bg_duration_sigma=jnp.float32(p.bg_duration_sigma),
        bg_backlog=jnp.float32(max(round(p.bg_initial_backlog * shrink), 1)),
        bg_burst_mean=jnp.float32(p.bg_burst_mean),
    )


@dataclass(frozen=True)
class XSimConfig:
    """Static shape/budget parameters shared by a whole grid."""

    n_warm: int = 48         # warm-start running-job slots
    n_backlog: int = 32      # queued-backlog slots
    n_arrivals: int = 64     # future background-arrival slots
    max_stages: int = 9      # Montage has 9
    t0: float = 7200.0       # workflow submission epoch (runner.WARMUP_S)
    horizon: float = 10 * 86400.0  # arrivals beyond this are dropped
    warm_fill: float = 0.97  # warm-start capacity target (QueueSim's 97%)
    pred_mode: str = "greedy"  # cascade a_y: live MAP ("greedy") or the
    #   Algorithm-1 line-4 draw ("sample"). Fleet sweeps default to the
    #   consistent MAP — the estimator still learns (and the MAP moves)
    #   within the run; i.i.d. draws from a still-multi-modal p can delay
    #   a successor by the full bin gap. "sample" matches the event-driven
    #   tuned runner call-for-call (cross-validation uses state.freeze).
    chunk_steps: int = 8     # scan-chunk size between drain-exit checks
    #   (events.simulate): smaller = finer early exit, larger = fewer
    #   while_loop round-trips; 0 disables chunking (one static scan).
    #   Bit-identical results for every value — drained steps are no-ops.
    trace_capacity: int = 0  # event-ring slots per scenario
    #   (repro.obs.trace); 0 = untraced, statically — no trace ops are
    #   ever staged and the sweep is the pre-observability program.
    n_faults: int = 0        # capacity-fault slots per scenario
    #   (runtime.fault.FaultSchedule events); 0 = no fault machinery is
    #   ever staged and the sweep is the pre-faults program, bit for bit.

    def __post_init__(self) -> None:
        if self.pred_mode not in ("greedy", "sample"):
            raise ValueError(f"unknown pred_mode {self.pred_mode!r}")
        if self.chunk_steps < 0:
            raise ValueError(f"chunk_steps must be >= 0, got "
                             f"{self.chunk_steps}")
        if self.trace_capacity < 0:
            raise ValueError(f"trace_capacity must be >= 0, got "
                             f"{self.trace_capacity}")
        if self.n_faults < 0:
            raise ValueError(f"n_faults must be >= 0, got {self.n_faults}")

    @property
    def max_jobs(self) -> int:
        return self.n_warm + self.n_backlog + self.n_arrivals + self.max_stages

    def with_trace(self, capacity: int | None = None) -> "XSimConfig":
        """This config with event tracing on. The default capacity —
        4·max_jobs — covers the worst event sequence a scenario can emit
        (submit + start + finish per job, plus the naive cancel/resubmit
        detours) with slack, so rings normally never overflow."""
        import dataclasses

        if capacity is None:
            capacity = 4 * self.max_jobs
        elif capacity < 1:
            # an explicit "trace with no room" is a contradiction, not a
            # request to disable tracing (that is the default config)
            raise ValueError(f"with_trace needs trace_capacity >= 1, "
                             f"got {capacity}")
        return dataclasses.replace(self, trace_capacity=capacity)

    @property
    def n_steps(self) -> int:
        """Safe event budget: each job costs at most one admission step
        and one completion step (same-instant admissions batch, and the
        in-step hook drain absorbs whole stage cascades into their
        admission step), plus the naive cancel/resubmit detours — every
        stage can cancel at most once, and a cancel adds one repass step
        plus one same-instant resubmission-admission step, hence the
        ``2·max_stages`` slack (+16 base cushion). The old
        ``6·max_stages`` same-instant-cascade term is gone — that is the
        step-budget half of the event-bound optimization — and the
        chunked drain exit makes any remaining overcount nearly free
        (drained scenarios stop stepping, so only truly long scenarios
        ever touch the budget tail). Each capacity fault costs one event
        step of its own plus, in the worst FAIL case, one extra
        completion-and-restart step per killed-and-requeued job — hence
        the ``n_faults · (1 + max_jobs)`` term."""
        return (2 * self.max_jobs + 2 * self.max_stages + 16
                + self.n_faults * (1 + self.max_jobs))


def build_scenario(key: jax.Array, center: XCenter, wf_cores: jax.Array,
                   wf_durs: jax.Array, wf_valid: jax.Array,
                   est, policy: jax.Array, fault_t: jax.Array,
                   fault_c: jax.Array, fault_k: jax.Array,
                   cfg: XSimConfig) -> ScenarioState:
    """One scenario as a pure function of (key, cell data). vmap freely.

    ``est`` is the scenario's live Algorithm-1 estimator (its geometry's
    fleet slice, see ``policies.scenario_estimators``) — predictions are
    sampled from it, and it learns, inside the event scan.
    ``fault_t``/``fault_c``/``fault_k`` are the scenario's capacity-fault
    schedule as (cfg.n_faults,) arrays (``FaultSchedule.as_arrays``)."""
    k_warm_c, k_warm_d, k_warm_u, k_back_c, k_back_d, k_arr_g, k_arr_b, \
        k_arr_c, k_arr_d = jax.random.split(key, 9)
    total = center.total_cores

    def widths(k, n):
        w = jnp.exp(center.bg_cores_mean
                    + center.bg_cores_sigma * jax.random.normal(k, (n,)))
        return jnp.clip(jnp.round(w), 1.0, jnp.maximum(total // 2, 1.0))

    def durations(k, n):
        d = jnp.exp(center.bg_duration_mean_s
                    + center.bg_duration_sigma * jax.random.normal(k, (n,)))
        return jnp.clip(d, 30.0, 7.0 * 86400.0)

    # --- warm start: machine filled to ~warm_fill with residual jobs ----
    wc = widths(k_warm_c, cfg.n_warm)
    wd = durations(k_warm_d, cfg.n_warm)
    w_ok = jnp.cumsum(wc) <= cfg.warm_fill * total
    wc = jnp.where(w_ok, wc, 0.0)
    w_end = jax.random.uniform(k_warm_u, (cfg.n_warm,), minval=0.05,
                               maxval=1.0) * wd
    free = total - jnp.sum(wc)

    # --- backlog: queued at t=0, FCFS position = row order --------------
    bc = widths(k_back_c, cfg.n_backlog)
    bd = durations(k_back_d, cfg.n_backlog)
    b_ok = jnp.arange(cfg.n_backlog) < center.bg_backlog

    # --- future arrivals: Poisson bursts --------------------------------
    gaps = jax.random.exponential(k_arr_g, (cfg.n_arrivals,)) \
        / center.bg_arrival_rate
    group_t = jnp.cumsum(gaps)
    u = jax.random.uniform(k_arr_b, (cfg.n_arrivals,), minval=1e-6,
                           maxval=1.0 - 1e-6)
    p_burst = 1.0 / jnp.maximum(center.bg_burst_mean, 1.0)
    burst = jnp.where(
        center.bg_burst_mean <= 1.0, 1.0,
        jnp.floor(jnp.log(u) / jnp.log1p(-p_burst)) + 1.0)
    group_of = jnp.searchsorted(jnp.cumsum(burst),
                                jnp.arange(cfg.n_arrivals), side="right")
    a_submit = group_t[jnp.clip(group_of, 0, cfg.n_arrivals - 1)]
    ac = widths(k_arr_c, cfg.n_arrivals)
    ad = durations(k_arr_d, cfg.n_arrivals)
    a_ok = a_submit <= cfg.horizon

    # --- workflow rows (policy is data: all variants, selected) ---------
    wf_off = cfg.n_warm + cfg.n_backlog + cfg.n_arrivals
    y = jnp.arange(cfg.max_stages)
    peak = jnp.max(wf_cores)
    total_dur = jnp.sum(jnp.where(wf_valid, wf_durs, 0.0))
    n_stages = jnp.sum(wf_valid.astype(jnp.float32))
    useful_cs = jnp.sum(jnp.where(wf_valid, wf_cores * wf_durs, 0.0))
    is_big = policy == BIGJOB
    is_pilot = policy == PILOT
    # BigJob and the pilot both submit ONE peak-cores monolith; the pilot
    # additionally pays its bootstrap + per-stage internal dispatch
    # latency on the walltime (strategies.pilot_duration, mirrored here)
    single = is_big | is_pilot
    pilot_dur = total_dur + PILOT_STARTUP_S + n_stages * PILOT_TASK_LATENCY_S
    single_dur = jnp.where(is_pilot, pilot_dur, total_dur)
    # ASA-Naive + the learned policy: cascade rows, no afterok edge
    no_dep = (policy == ASA_NAIVE) | (policy == RL)
    f_valid = jnp.where(single, y == 0, wf_valid)
    f_cores = jnp.where(single, jnp.where(y == 0, peak, 0.0), wf_cores)
    f_durs = jnp.where(single, jnp.where(y == 0, single_dur, 0.0), wf_durs)
    f_submit = jnp.where(y == 0, cfg.t0, jnp.inf)
    nxt_valid = jnp.concatenate([f_valid[1:], jnp.zeros(1, bool)])
    f_next = jnp.where(f_valid & nxt_valid & ~single, wf_off + y + 1, -1)
    f_dep = jnp.where(f_valid & (y > 0) & ~single & ~no_dep,
                      wf_off + y - 1, -1)
    f_rows = jnp.where(f_valid, wf_off + y, -1)
    waste_cs = jnp.where(is_pilot, peak * pilot_dur - useful_cs, 0.0)

    # --- assemble the table ---------------------------------------------
    def cat(warm, back, arr, wf):
        return jnp.concatenate([warm, back, arr, wf])

    zeros = jnp.zeros
    nwm, nbk, nar, nst = cfg.n_warm, cfg.n_backlog, cfg.n_arrivals, \
        cfg.max_stages
    inf = jnp.inf
    submit = cat(zeros(nwm), zeros(nbk), jnp.where(a_ok, a_submit, inf),
                 f_submit)
    cores = cat(wc, jnp.where(b_ok, bc, 0.0), jnp.where(a_ok, ac, 0.0),
                f_cores)
    duration = cat(wd, bd, ad, f_durs)
    start = cat(jnp.where(w_ok, 0.0, inf), jnp.full(nbk, inf),
                jnp.full(nar, inf), jnp.full(nst, inf))
    end = cat(jnp.where(w_ok, w_end, inf), jnp.full(nbk, inf),
              jnp.full(nar, inf), jnp.full(nst, inf))
    status = cat(jnp.where(w_ok, RUNNING, INVALID),
                 jnp.where(b_ok, QUEUED, INVALID),
                 jnp.where(a_ok, PENDING, INVALID),
                 jnp.where(f_valid, PENDING, INVALID)).astype(jnp.int32)
    start_dep = cat(jnp.full(nwm, -1), jnp.full(nbk, -1), jnp.full(nar, -1),
                    f_dep).astype(jnp.int32)
    wf_next = cat(jnp.full(nwm, -1), jnp.full(nbk, -1), jnp.full(nar, -1),
                  f_next).astype(jnp.int32)
    is_wf = cat(zeros(nwm, bool), zeros(nbk, bool), zeros(nar, bool),
                f_valid)

    return ScenarioState(
        submit=submit, cores=cores, duration=duration, start=start, end=end,
        status=status, start_dep=start_dep, wf_next=wf_next, is_wf=is_wf,
        pred_wait=zeros(cfg.max_jobs),
        expected_end=jnp.full(cfg.max_jobs, -jnp.inf),
        wf_rows=f_rows.astype(jnp.int32),
        hold=zeros(cfg.max_stages),
        canc_start=jnp.full(cfg.max_stages, jnp.inf),
        start_pending=zeros(cfg.max_stages, bool),
        chain_pending=zeros(cfg.max_stages, bool),
        rl_obs=zeros((cfg.max_stages, RL_FEATURES)),
        rl_act=jnp.full(cfg.max_stages, -1, jnp.int32),
        est=est,
        t=jnp.float32(0.0), free=free, total=total,
        policy=policy.astype(jnp.int32), t0=jnp.float32(cfg.t0),
        busy_cs=jnp.float32(0.0), min_free=free,
        oh_cs=jnp.float32(0.0), misses=jnp.int32(0),
        repass=jnp.asarray(False),
        pred_greedy=jnp.asarray(cfg.pred_mode == "greedy"),
        steps=jnp.int32(0),
        fault_t=fault_t.astype(jnp.float32),
        fault_c=fault_c.astype(jnp.float32),
        fault_k=fault_k.astype(jnp.int32),
        fault_next=jnp.int32(0),
        cap_debt=jnp.float32(0.0),
        restarts=jnp.int32(0),
        restart_cs=jnp.float32(0.0),
        pilot_waste_cs=waste_cs.astype(jnp.float32),
        trace=(obs_trace.init(cfg.trace_capacity)
               if cfg.trace_capacity else None),
    )


build_batch = jax.jit(
    jax.vmap(build_scenario, in_axes=(0,) * 10 + (None,)),
    static_argnums=(10,))


@dataclass
class ScenarioGrid:
    """A flat batch of scenarios + the cell labels that produced them."""

    cfg: XSimConfig
    keys: jax.Array               # (B, 2) PRNG keys
    centers: XCenter              # stacked (B,)
    wf_cores: jax.Array           # (B, S)
    wf_durs: jax.Array            # (B, S)
    wf_valid: jax.Array           # (B, S)
    policies: jax.Array           # (B,)
    fault_t: jax.Array            # (B, n_faults) fault times, +inf pad
    fault_c: jax.Array            # (B, n_faults) core deltas (>= 0)
    fault_k: jax.Array            # (B, n_faults) FAULT_* kinds
    geo_idx: np.ndarray           # (B,) geometry id (center, scale) per row
    labels: list[dict]            # per-scenario {center, scale, workflow, ...}

    @property
    def n(self) -> int:
        return int(self.policies.shape[0])

    @property
    def has_faults(self) -> bool:
        """Static: any fault slots at all (cfg.n_faults > 0). Statically
        False elides the whole fault path from the swept program."""
        return int(self.fault_t.shape[1]) > 0

    def build(self, ests) -> ScenarioState:
        """``ests`` is a (B,)-batched ASAState (per-scenario estimators)."""
        return build_batch(self.keys, self.centers, self.wf_cores,
                           self.wf_durs, self.wf_valid, ests,
                           self.policies, self.fault_t, self.fault_c,
                           self.fault_k, self.cfg)


def make_grid(cfg: XSimConfig,
              center_names: Sequence[str] = ("hpc2n", "uppmax"),
              workflows: Sequence[str | Workflow] =
              ("montage", "blast", "statistics"),
              policy_ids: Sequence[int] = (0, 1, 2),
              n_seeds: int = 4, shrink: float = 1.0 / 64.0,
              scales: Sequence[int] | None = None,
              seed: int = 0, fault_sched=None) -> ScenarioGrid:
    """The full scenario product, flattened to one batch.

    Cells = centers × their paper scales × workflows × policies × seeds.
    ``shrink`` miniaturizes the centers (default 1/64: HPC2n → 263 cores)
    so the slotted tables stay small; workflow scales shrink alongside.
    ``workflows`` entries are names in ``WORKFLOWS`` or ``Workflow``
    instances (custom stage profiles, e.g. single-stage probes).

    ``fault_sched`` injects capacity faults (``cfg.n_faults`` must cover
    the longest schedule): a ``runtime.fault.FaultSchedule`` applied to
    every scenario, or a callable ``label_dict -> FaultSchedule`` for
    per-scenario schedules (see ``repro.xsim.families`` for the standard
    robustness families). Event ``frac`` values are fractions of the
    center's *original* (shrunk) total cores, converted to whole cores
    host-side here.
    """
    cells, labels, geo, bg_keys, faults = [], [], [], [], []
    if fault_sched is not None and cfg.n_faults == 0:
        raise ValueError("fault_sched given but cfg.n_faults == 0; set "
                         "XSimConfig(n_faults=...) to size the fault slots")
    base = jax.random.PRNGKey(seed)
    geo_ids: dict[tuple[str, int], int] = {}
    for cname in center_names:
        profile = CENTERS[cname]
        total_cores = float(max(profile.total_cores * shrink, 8.0))
        for scale in (scales or profile.scales):
            eff_scale = max(int(round(scale * shrink)), 2)
            gid = geo_ids.setdefault((cname, scale), len(geo_ids))
            for w in workflows:
                wf = w if isinstance(w, Workflow) else WORKFLOWS[w]
                sc, sd, sv = policies.stage_arrays(
                    wf, eff_scale, cfg.max_stages)
                for pol in policy_ids:
                    for s in range(n_seeds):
                        cells.append((profile, sc, sd, sv, pol))
                        geo.append(gid)
                        # background depends ONLY on (geometry, seed):
                        # strategies and workflows of one cell see the
                        # identical machine, as run_table1 does
                        bg_keys.append(jax.random.fold_in(
                            base, gid * 100_003 + s))
                        lab = dict(center=cname, scale=scale,
                                   workflow=wf.name,
                                   strategy=POLICY_NAMES[pol],
                                   seed=s)
                        labels.append(lab)
                        sched = (fault_sched(lab) if callable(fault_sched)
                                 else fault_sched) or FaultSchedule()
                        faults.append(sched.as_arrays(cfg.n_faults,
                                                      total_cores))
    B = len(cells)
    if B == 0:
        raise ValueError(
            "empty scenario grid: the centers × scales × workflows × "
            "policies × seeds product has no cells "
            f"(centers={list(center_names)!r}, workflows={list(workflows)!r},"
            f" policy_ids={list(policy_ids)!r}, n_seeds={n_seeds})")
    stacked_centers = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[center_params(c[0], shrink) for c in cells])
    return ScenarioGrid(
        cfg=cfg,
        keys=jnp.stack(bg_keys),
        centers=stacked_centers,
        wf_cores=jnp.stack([jnp.asarray(c[1]) for c in cells]),
        wf_durs=jnp.stack([jnp.asarray(c[2]) for c in cells]),
        wf_valid=jnp.stack([jnp.asarray(c[3]) for c in cells]),
        policies=jnp.asarray([c[4] for c in cells], jnp.int32),
        fault_t=jnp.stack([jnp.asarray(f[0]) for f in faults]),
        fault_c=jnp.stack([jnp.asarray(f[1]) for f in faults]),
        fault_k=jnp.stack([jnp.asarray(f[2]) for f in faults]),
        geo_idx=np.asarray(geo),
        labels=labels,
    )


def initial_states(grid: ScenarioGrid, fleet=None,
                   pred_seed: int = 1) -> ScenarioState:
    """The batched input tables ``run_grid`` sweeps: each scenario with
    its geometry's slice of ``fleet`` (a fresh fleet when None) as its
    live estimator, PRNG-decorrelated by ``pred_seed``."""
    if fleet is None:
        fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    ests = policies.scenario_estimators(
        fleet, jnp.asarray(grid.geo_idx), pred_seed)
    return grid.build(ests)


def run_grid(grid: ScenarioGrid, fleet=None, *, pred_seed: int = 1,
             bf_passes: int = backfill.BF_PASSES,
             freed_mode: str = "ref", params=None,
             rl_mode: str = "sample", n_shards: int | None = None,
             mesh=None):
    """Build + sweep the whole grid in one jitted batched program.

    ``fleet`` is a batched ASAState (one estimator per geometry); when
    None a fresh fleet is initialised (cold estimators). Every scenario
    carries its geometry's live estimator slice through the scan —
    predictions are sampled, and learning happens, *within* the run;
    ``pred_seed`` decorrelates the per-scenario PRNG streams across
    sweeps. ``freed_mode`` selects the reservation-scan backend
    (``"tpu"`` = Pallas kernel). ``params`` is the learned submission
    policy's weight pytree — required when the grid contains policy id 4
    scenarios; ``rl_mode`` picks sampled (training) vs greedy
    (evaluation) actions for them.

    ``n_shards`` / ``mesh`` select the device-parallel path: the scenario
    axis is shard_mapped over a 1-D ``scenarios`` mesh (``mesh`` wins
    when both are given; ``n_shards`` builds one over the first N visible
    devices via ``launch.mesh.make_scenarios_mesh``, validating N against
    the device inventory). Batches not divisible by the shard count are
    padded and the pad rows dropped; the result is bit-identical to the
    default single-device vmap (both pinned by test). Returns
    (final_states, metrics dict of (B,) arrays).
    """
    from repro.xsim import compare

    pols = np.asarray(grid.policies)
    if params is None and bool(np.any(pols == RL)):
        raise ValueError(
            "grid contains learned-policy (rl, id 4) scenarios; pass "
            "params= (repro.rl.policy.PolicyParams) to run_grid")
    if rl_mode not in ("sample", "greedy"):
        raise ValueError(f"unknown rl_mode {rl_mode!r}")
    if mesh is None and n_shards is not None:
        from repro.launch.mesh import make_scenarios_mesh
        mesh = make_scenarios_mesh(n_shards)
    states = initial_states(grid, fleet, pred_seed)
    # RL shares ASA-Naive's no-dependency world (cancel/resubmit machinery)
    has_naive = bool(np.any((pols == ASA_NAIVE) | (pols == RL)))
    kw = dict(n_steps=grid.cfg.n_steps, chunk_steps=grid.cfg.chunk_steps,
              bf_passes=bf_passes, freed_mode=freed_mode,
              pred_mode=grid.cfg.pred_mode, naive=has_naive, params=params,
              rl_mode=rl_mode, faults=grid.has_faults)
    if mesh is None:
        final = events.sweep(states, **kw)
    else:
        final = events.sharded_sweep(states, mesh=mesh, **kw)
    # metrics always run on the gathered final states: the sweep itself
    # is bit-identical across shard counts, so this keeps the metrics
    # bit-identical too (compare.sharded_batched_metrics reduces on the
    # shards instead, at the price of ~1-ULP reduction-order wiggle)
    return final, compare.batched_metrics(final)


def stage_waits(final: ScenarioState, cfg: XSimConfig
                ) -> tuple[np.ndarray, np.ndarray]:
    """(waits, valid) of shape (B, max_stages) from a batched final state."""
    sl = slice(cfg.max_jobs - cfg.max_stages, cfg.max_jobs)
    waits = np.asarray(final.start[:, sl] - final.submit[:, sl])
    valid = np.asarray(final.is_wf[:, sl]) & np.isfinite(waits)
    return waits, valid


def warm_fleet(fleet, grid: ScenarioGrid, rounds: int = 2, k: int = 8,
               seed: int = 100, params=None, n_shards: int | None = None,
               mesh=None):
    """§4.3 cross-run persistence: sweep, observe first-stage waits (a
    clean per-geometry queue sample), update every geometry's estimator,
    repeat. Returns the warmed fleet. ``params`` is forwarded to
    ``run_grid`` (required only when the grid contains learned-policy
    scenarios); ``n_shards``/``mesh`` likewise select its device-parallel
    sweep path."""
    n_geo = fleet.log_p.shape[0]
    # BigJob's and the pilot's row 0 is the peak-cores monolith, not a
    # stage-shaped job — exclude them so each geometry learns from clean
    # stage-0 samples
    stagelike = np.array([lab["strategy"] not in ("bigjob", "pilot")
                          for lab in grid.labels])
    if mesh is None and n_shards is not None:
        from repro.launch.mesh import make_scenarios_mesh
        mesh = make_scenarios_mesh(n_shards)
    for r in range(rounds):
        final, _ = run_grid(grid, fleet, pred_seed=seed + r, params=params,
                            mesh=mesh)
        waits, valid = stage_waits(final, grid.cfg)
        W = np.zeros((n_geo, k), np.float32)
        V = np.zeros((n_geo, k), bool)
        for g in range(n_geo):
            sel = (grid.geo_idx == g) & stagelike
            w = waits[sel, 0]
            w = w[valid[sel, 0]][:k]
            W[g, :len(w)] = w
            V[g, :len(w)] = True
        fleet = policies.update_fleet(fleet, jnp.asarray(W), jnp.asarray(V))
    return fleet
