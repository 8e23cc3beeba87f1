"""Metrics extraction + QueueSim cross-validation bridge.

``metrics`` reduces a finished ScenarioState to the same quantities
``sched.runner``'s RunMetrics carries (twt_s, makespan_s, core_hours,
oh_hours, utilization) so ``benchmarks/`` can consume either engine.
``scenario_from_queue_sim`` snapshots a live event-driven QueueSim into an
xsim job table — the cross-validation tests run both engines from the
*identical* machine state and compare the numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.xsim.state import (ASA, ASA_NAIVE, DONE, PILOT, QUEUED, RL,
                              RUNNING, ScenarioState, empty_table)


def metrics(s: ScenarioState) -> dict[str, jax.Array]:
    """Per-scenario scalars (vmap over a batched state for fleet metrics).

    twt_s is policy-aware: BigJob = the single job's wait, Per-Stage =
    Σ stage waits, ASA / ASA-Naive / the learned policy = *perceived*
    waits along the stage chain (stage 0's full wait, then the part of
    each stage's wait not hidden behind its predecessor's logical end,
    which includes any naive idle hold) — matching
    ``sched.strategies.run_asa``'s settled-timeline bookkeeping exactly.
    Pilot (policy 5) counts like BigJob (single job wait / wf end).

    oh_hours carries the naive/RL over-allocation, plus — for the pilot
    policy — the pilot's packing waste (charged once the pilot actually
    starts, mirroring ``run_pilot``), plus the core-seconds lost to
    fault kills (work the killed attempts consumed before restarting).
    The pilot's waste is already *inside* its single row's
    cores × duration, so its core_hours does NOT re-add oh_hours —
    preserving the CH(pilot) == CH(asa) + OH(pilot) identity that
    ``run_pilot`` satisfies on the event engine.
    """
    n = s.status.shape[0]
    wf = s.is_wf
    wait = jnp.where(wf, s.start - s.submit, 0.0)
    wait_sum = jnp.sum(jnp.where(wf, wait, 0.0))

    # ASA/naive perceived waits + logical makespan: walk the stage chain,
    # carrying the logical end  le_y = max(start_y + hold_y, le_{y−1}) + t_y
    # (run_asa's settled timeline; hold is 0 everywhere but naive misses).
    rows = jnp.clip(s.wf_rows, 0, n - 1)

    def chain(y, carry):
        le, twt = carry
        row = rows[y]
        ok = (s.wf_rows[y] >= 0) & jnp.isfinite(s.start[row])
        start_l = s.start[row] + s.hold[y]
        # a naive stage can start while an earlier stage never did (no
        # afterok edge + exhausted step budget): its predecessor logical
        # end is still -inf — count no perceived wait rather than +inf
        pwt = jnp.where(y == 0, s.start[row] - s.submit[row],
                        jnp.where(jnp.isneginf(le), 0.0,
                                  jnp.maximum(s.start[row] - le, 0.0)))
        new_le = jnp.where(y == 0, start_l,
                           jnp.maximum(start_l, le)) + s.duration[row]
        return (jnp.where(ok, new_le, le), twt + jnp.where(ok, pwt, 0.0))

    le, chain_twt = jax.lax.fori_loop(
        0, s.wf_rows.shape[0], chain,
        (jnp.float32(-jnp.inf), jnp.float32(0.0)))

    asa_like = ((s.policy == ASA) | (s.policy == ASA_NAIVE)
                | (s.policy == RL))
    twt = jnp.where(asa_like, chain_twt, wait_sum)

    wf_end = jnp.max(jnp.where(wf, s.end, -jnp.inf))
    makespan = jnp.where(asa_like, le, wf_end) - s.t0
    core_seconds = jnp.sum(jnp.where(wf, s.cores * s.duration, 0.0))
    restart_hours = s.restart_cs / 3600.0
    is_pilot = s.policy == PILOT
    started_any = jnp.any(wf & jnp.isfinite(s.start))
    pilot_oh = jnp.where(started_any, s.pilot_waste_cs, 0.0) / 3600.0
    oh_hours = jnp.where(is_pilot, pilot_oh,
                         s.oh_cs / 3600.0) + restart_hours
    core_hours = core_seconds / 3600.0 + jnp.where(is_pilot, restart_hours,
                                                   oh_hours)
    done = jnp.sum((wf & (s.status == DONE)).astype(jnp.int32))
    total_wf = jnp.sum(wf.astype(jnp.int32))
    util = s.busy_cs / jnp.maximum(s.total * s.t, 1e-9)
    return {
        "twt_s": twt,
        "makespan_s": makespan,
        "core_hours": core_hours,
        "oh_hours": oh_hours,
        "misses": s.misses,
        "utilization": util,
        "wf_done": done,
        "wf_total": total_wf,
        "restarts": s.restarts,
        "restart_hours": restart_hours,
        "policy": s.policy,
    }


batched_metrics = jax.jit(jax.vmap(metrics))


def sharded_batched_metrics(final: ScenarioState, mesh
                            ) -> dict[str, jax.Array]:
    """``batched_metrics`` under a 1-D ``scenarios`` mesh: each device
    reduces its own block of final states to the per-scenario metric
    scalars, and only the small (B,) columns are gathered — for fleets
    whose final states live sharded across devices (same padding
    semantics as ``events.sharded_sweep``). Values match the vmap path
    up to reduction order (~1 ULP on the summed columns: XLA associates
    the per-scenario sums differently per block shape), which is why
    ``run_grid`` — whose contract is bitwise device-count independence —
    computes metrics on the gathered states instead."""
    from repro.parallel import fleet as pfleet

    n_shards = mesh.shape[pfleet.SCENARIO_AXIS]
    b = pfleet.batch_size(final)
    padded, _mask = pfleet.pad_batch(final, n_shards)
    spec = pfleet.shard_spec()
    fn = jax.shard_map(jax.vmap(metrics), mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    return pfleet.unpad(fn(padded), b)


def wf_rows(s: ScenarioState) -> dict[str, np.ndarray]:
    """Host-side view of the workflow rows (stage-ordered), for tests."""
    mask = np.asarray(s.is_wf)
    out = {}
    for name in ("submit", "start", "end", "cores", "duration", "status"):
        out[name] = np.asarray(getattr(s, name))[mask]
    return out


def scenario_from_queue_sim(sim, max_jobs: int) -> tuple[dict, int]:
    """Snapshot a live QueueSim into a host-side xsim job table.

    Returns (table, next_free_row). Running jobs keep their residual end
    times; queued jobs keep their submit times and FCFS positions (row
    order = queue order, and xsim's stable sort preserves it for equal
    submit times). Workflow rows are appended by the caller via
    ``policies.add_workflow`` starting at next_free_row.
    """
    table = empty_table(max_jobs)
    row = 0
    for _, jid in sorted(sim.running):
        j = sim.jobs[jid]
        if jid in sim.finished or j.canceled:
            continue
        table["submit"][row] = j.submit_time
        table["cores"][row] = j.cores
        table["duration"][row] = j.duration
        table["start"][row] = j.start_time
        table["end"][row] = j.end_time
        table["status"][row] = RUNNING
        row += 1
    for jid in sim.queue:
        j = sim.jobs[jid]
        table["submit"][row] = j.submit_time
        table["cores"][row] = j.cores
        table["duration"][row] = j.duration
        table["status"][row] = QUEUED
        row += 1
    return table, row


def queue_sim_free_cores(sim) -> float:
    return float(sim.free_cores)
