"""Event-time advance + arrival/completion kernels + the `lax.scan` step.

One ``sim_step`` jumps to the next event time (earliest pending submission
or running-job completion), then applies, as masked array writes:

  completions → per-stage release hook → naive resubmit release →
  admissions → FCFS/backfill scheduling pass → stage-start hook →
  ASA chain hook.

Same-time cascades (e.g. a per-stage successor released *at* the
completion instant) simply consume the next scan step at an unchanged
``now`` — steps are cheap, so the step budget absorbs them. A scenario
with no remaining events makes every further step a no-op, which lets a
whole vmapped batch run the same static step count.

Policy hooks (kept here, not in policies.py, because they are part of the
per-event dataflow):

* PER_STAGE: when stage y completes, stage y+1's submit time becomes
  "now" — the sequential submit-on-completion loop of
  ``strategies.run_per_stage``.
* ASA / ASA-Naive *chain* hook: when stage y is first admitted at time
  s_y, the wait estimate a_y (stage 0 only; later stages were sampled at
  their predecessor's admission) and the successor's a_{y+1} are sampled
  from the scenario's LIVE Algorithm-1 estimator, the expected end
  E_y = max(s_y + a_y, E_{y-1}) + t_y chains forward, and stage y+1 is
  scheduled for max(now, E_y − a_{y+1}) — exactly the cascade of
  ``strategies.run_asa`` (§3.2, Fig. 4), now learning within the run.
* ASA / ASA-Naive *start* hook: when stage y starts, its observed queue
  wait feeds the tuned §4.5 estimator update (``asa.learn_wait_if``).
  Under ASA-Naive (no dependency support) an allocation granted before
  stage y−1's logical end either idles (short gaps ≤ 300 s, charged as
  OH core-seconds) or is CANCELLED and resubmitted once the predecessor
  completes (long gaps), charging the cancel latency as OH — mirroring
  ``strategies.run_asa(use_dependencies=False)``.
* Learned policy (``repro.rl``, policy id 4): same hooks as ASA-Naive
  (no-dependency world, estimator still learning), but the chain hook's
  wait estimates come from an MLP head over the same wait bins when a
  ``params`` pytree is threaded through the sweep — observations and
  chosen bins are recorded into the ``rl_obs``/``rl_act`` replay
  buffers. ``params=None`` statically elides the branch.

The start/chain hooks are drained INSIDE one ``sim_step``: a bounded
inner loop processes one (start, chain) pair per iteration — estimator
updates are inherently sequential (each consumes PRNG state), so the
pair-at-a-time order is exactly the order the old repass mechanism
produced and the cross-validation tests pin action-for-action — but a
multi-stage same-instant cascade no longer pays a full scan step
(completion scan + scheduling pass) per stage. The ``repass`` flag
survives for the one case that genuinely must reschedule mid-instant:
a naive/RL cancel frees cores (and possibly queues a same-instant
resubmission), so the drain exits and the next step re-runs the
scheduling pass at the unchanged ``now``, exactly as before.

``simulate`` runs the scan in K-step chunks under an outer
``lax.while_loop`` that exits once ``next_event_time`` is +inf — a
drained scenario stops paying for dead budget steps. Under ``vmap`` the
exit condition any-reduces across the batch (and per device under
``sharded_sweep``), and drained lanes step as exact no-ops, so the final
states stay bit-identical across chunk boundaries and device counts.

``sweep`` is the single-device fleet program (vmap over the batch);
``sharded_sweep`` shard_maps the same program's scenario axis over a 1-D
``scenarios`` device mesh — bit-identical, scenarios never communicate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import asa
from repro.core.bins import make_bins
from repro.obs import trace as obs_trace
from repro.runtime.fault import FAULT_DRAIN, FAULT_FAIL, FAULT_GROW
from repro.sched.strategies import (NAIVE_CANCEL_LATENCY_S,
                                    NAIVE_IDLE_THRESHOLD_S)
from repro.xsim import backfill
from repro.xsim.state import (ASA, ASA_NAIVE, CANCELLED, DONE, PENDING,
                              PER_STAGE, QUEUED, RL, RUNNING, ScenarioState)


def _asa_like(s: ScenarioState) -> jax.Array:
    """Policies that run the cascade hooks (chain + start + estimator)."""
    return (s.policy == ASA) | (s.policy == ASA_NAIVE) | (s.policy == RL)


def _naive_like(s: ScenarioState) -> jax.Array:
    """Policies without dependency support: early allocations idle or are
    cancelled/resubmitted (§4.5). The learned policy (repro.rl) lives in
    this world — the over-allocation OH is what makes its
    submit-lead-time problem non-degenerate."""
    return (s.policy == ASA_NAIVE) | (s.policy == RL)


def _job_stage(s: ScenarioState) -> jax.Array:
    """i32 (max_jobs,) workflow stage index per row; -1 for background."""
    n = s.status.shape[0]
    y = jnp.arange(s.wf_rows.shape[0], dtype=jnp.int32)
    tgt = jnp.where(s.wf_rows >= 0, s.wf_rows, n)   # n = drop
    return jnp.full(n, -1, jnp.int32).at[tgt].set(y, mode="drop")


def next_event_time(s: ScenarioState, naive: bool = True,
                    faults: bool = False) -> jax.Array:
    """Earliest pending submit, running end or unprocessed capacity fault;
    +inf when nothing remains.

    CANCELLED rows with a finite submit are naive resubmissions waiting
    for their corrected time; ``repass`` pins the next step to the current
    instant (mid-event estimator/cancel cascades). ``faults=False``
    (static) elides the fault-schedule term entirely. Its device work is
    named ``xsim.events`` wherever it runs, ``simulate``'s drain check
    included."""
    with jax.named_scope("xsim.events"):
        submittable = s.status == PENDING
        if naive:
            submittable |= s.status == CANCELLED
        submits = jnp.where(submittable, s.submit, jnp.inf)
        ends = jnp.where(s.status == RUNNING, s.end, jnp.inf)
        nxt = jnp.minimum(jnp.min(submits), jnp.min(ends))
        if faults and s.fault_t.shape[0]:
            nf = s.fault_t.shape[0]
            i = jnp.clip(s.fault_next, 0, nf - 1)
            ft = jnp.where(s.fault_next < nf, s.fault_t[i], jnp.inf)
            nxt = jnp.minimum(nxt, ft)
        return jnp.where(s.repass, s.t, nxt)


def complete_jobs(s: ScenarioState, now, faults: bool = False
                  ) -> tuple[ScenarioState, jax.Array]:
    done = (s.status == RUNNING) & (s.end <= now)
    freed = jnp.sum(jnp.where(done, s.cores, 0.0))
    if faults:
        # draining nodes leave as their work completes: freed cores pay
        # outstanding drain debt before returning to the free pool
        pay = jnp.minimum(freed, s.cap_debt)
        s = s._replace(status=jnp.where(done, DONE, s.status),
                       free=s.free + freed - pay, total=s.total - pay,
                       cap_debt=s.cap_debt - pay)
    else:
        s = s._replace(status=jnp.where(done, DONE, s.status),
                       free=s.free + freed)
    return s, done


def admit_jobs(s: ScenarioState, now, naive: bool = True
               ) -> tuple[ScenarioState, jax.Array]:
    submittable = s.status == PENDING
    if naive:  # resubmitted CANCELLED rows re-enter the queue
        submittable |= s.status == CANCELLED
    adm = submittable & (s.submit <= now)
    s = s._replace(status=jnp.where(adm, QUEUED, s.status))
    return s, adm


def _release_per_stage(s: ScenarioState, newly_done, now) -> ScenarioState:
    """Stage y DONE ⇒ stage y+1 submitted now (submit-on-completion)."""
    n = s.status.shape[0]
    fire = newly_done & s.is_wf & (s.policy == PER_STAGE) & (s.wf_next >= 0)
    succ = jnp.where(fire, s.wf_next, n)  # n = drop
    submit = s.submit.at[succ].set(now, mode="drop")
    return s._replace(submit=submit)


def _release_naive_resubmit(s: ScenarioState, newly_done, now
                            ) -> tuple[ScenarioState, jax.Array, jax.Array]:
    """Stage y DONE ⇒ a CANCELLED successor is resubmitted now (§4.5).

    Also returns ``(fire, succ_c)`` — the firing predecessor lanes and
    their (clipped) successor rows — so ``sim_step`` can fold the
    RESUBMIT events into its fused trace append."""
    n = s.status.shape[0]
    succ_c = jnp.clip(s.wf_next, 0, n - 1)
    fire = (newly_done & s.is_wf & _naive_like(s)
            & (s.wf_next >= 0) & (s.status[succ_c] == CANCELLED))
    succ = jnp.where(fire, s.wf_next, n)
    submit = s.submit.at[succ].set(now, mode="drop")
    return s._replace(submit=submit), fire, succ_c


def _apply_faults(s: ScenarioState, now) -> ScenarioState:
    """Process every capacity-fault event due at ``now``, in schedule order.

    One bounded ``while_loop`` iteration per due event (events are
    time-sorted at build; ``fault_next`` is the cursor). Semantics, with
    the conservation invariant ``total − free == Σ running cores`` held
    through every transition:

    * GROW d: nodes join — ``total += d``, ``free += d``.
    * DRAIN d (clamped to the machine present): what is free leaves now;
      the remainder becomes ``cap_debt``, collected by ``complete_jobs``
      from freed cores as running work finishes — a graceful shrink, no
      job is disturbed.
    * FAIL d (clamped): nodes die now. Free cores cover what they can;
      the deficit is covered by killing running jobs — most recently
      started first (LIFO, the cheapest work to lose; ties broken by row
      index), a deterministic rule that keeps the scan reproducible.
      Killed jobs are requeued in place (RUNNING → QUEUED, original
      submit time kept, so they retain their FCFS seniority, like a
      Slurm requeue) and restart from scratch; the attempt's lost
      core-seconds accrue to ``restart_cs`` and ``restarts`` counts the
      kills — ``compare.metrics`` reports both.

    Completions at the same instant land BEFORE the fault (a job ending
    exactly when the node dies finished); admissions and the scheduling
    pass land after, so requeued jobs can restart within the same step
    when capacity allows. A dynamically empty schedule (all +inf) never
    enters the loop: bit-identical to the fault-free program.
    """
    nf = s.fault_t.shape[0]
    if nf == 0:
        return s
    n = s.status.shape[0]

    def cond(s: ScenarioState):
        i = jnp.clip(s.fault_next, 0, nf - 1)
        return (s.fault_next < nf) & (s.fault_t[i] <= now)

    def body(s: ScenarioState):
        i = jnp.clip(s.fault_next, 0, nf - 1)
        d = s.fault_c[i]
        k = s.fault_k[i]
        is_grow = k == FAULT_GROW
        is_drain = k == FAULT_DRAIN
        is_fail = k == FAULT_FAIL
        # you can never lose more cores than are physically present
        d_s = jnp.minimum(d, s.total)

        # DRAIN: remove what is free now, owe the rest
        rm = jnp.minimum(s.free, d_s)

        # FAIL: kill most-recently-started running jobs to cover the
        # deficit (free cores absorb the loss first)
        deficit = jnp.where(is_fail, d_s - s.free, 0.0)
        running = s.status == RUNNING
        order = jnp.argsort(jnp.where(running, -s.start, jnp.inf))
        c_sorted = jnp.where(running, s.cores, 0.0)[order]
        csum = jnp.cumsum(c_sorted)
        kill_sorted = (csum - c_sorted < deficit) & (c_sorted > 0.0)
        kill = (jnp.zeros(n, bool).at[order].set(kill_sorted)
                & running & is_fail)
        killed = jnp.sum(jnp.where(kill, s.cores, 0.0))
        lost_cs = jnp.sum(jnp.where(kill, s.cores * (now - s.start), 0.0))

        free = jnp.where(
            is_grow, s.free + d,
            jnp.where(is_drain, s.free - rm,
                      jnp.where(is_fail, s.free + killed - d_s, s.free)))
        total = jnp.where(
            is_grow, s.total + d,
            jnp.where(is_drain, s.total - rm,
                      jnp.where(is_fail, s.total - d_s, s.total)))

        tr = s.trace
        if tr is not None:
            row_i = jnp.arange(n, dtype=jnp.int32)
            tr = obs_trace.append_segments(
                tr, [(kill, obs_trace.EV_KILL, row_i, _job_stage(s),
                      s.cores)], t=now, policy=s.policy, step=s.steps)
        return s._replace(
            trace=tr,
            free=free,
            total=total,
            min_free=jnp.minimum(s.min_free, free),
            cap_debt=s.cap_debt + jnp.where(is_drain, d_s - rm, 0.0),
            status=jnp.where(kill, QUEUED, s.status),
            start=jnp.where(kill, jnp.inf, s.start),
            end=jnp.where(kill, jnp.inf, s.end),
            restarts=s.restarts + jnp.sum(kill).astype(jnp.int32),
            restart_cs=s.restart_cs + lost_cs,
            fault_next=s.fault_next + 1,
        )

    return jax.lax.while_loop(cond, body, s)


def _start_hook(s: ScenarioState, now, bins, naive: bool) -> ScenarioState:
    """Process ONE pending stage start: naive early handling + learning.

    Mirrors ``strategies.run_asa``'s ``on_started``: compute the gap to
    the predecessor's *logical* end (start + hold + duration); a positive
    gap under ASA-Naive is a miss — short gaps idle the allocation
    (OH += cores·gap), long gaps cancel it (OH += cores·latency) and park
    the row as CANCELLED until the predecessor completes. Every settled
    start feeds the tuned estimator with the observed queue wait.
    ``naive=False`` (a static, batch-level guarantee that no scenario runs
    ASA-Naive) elides the miss machinery at trace time.
    """
    n = s.status.shape[0]
    pending = s.start_pending
    any_p = jnp.any(pending)
    y = jnp.argmax(pending)                     # lowest pending stage
    row = jnp.clip(s.wf_rows[y], 0, n - 1)
    wait = now - s.submit[row]                  # observed queue wait

    if not naive:
        return s._replace(
            est=asa.learn_wait_if(s.est, bins, wait, any_p),
            start_pending=pending.at[y].set(False),
        )

    yp = jnp.maximum(y - 1, 0)
    prev_row = jnp.where(y > 0, s.wf_rows[yp], -1)
    pc = jnp.clip(prev_row, 0, n - 1)
    prev_started = (prev_row >= 0) & jnp.isfinite(s.start[pc])
    # a cancelled-not-yet-resubmitted predecessor still projects a logical
    # end from its aborted attempt (QueueSim's jobs[y−1] keeps start_time
    # until the resubmission replaces it)
    prev_cancelled = ((prev_row >= 0) & (s.status[pc] == CANCELLED)
                      & jnp.isfinite(s.canc_start[yp]))
    prev_logical = jnp.where(
        prev_row < 0, -jnp.inf,
        jnp.where(prev_started, s.start[pc] + s.hold[yp] + s.duration[pc],
                  jnp.where(prev_cancelled,
                            s.canc_start[yp] + s.duration[pc], jnp.inf)))
    early = prev_logical - now
    is_early = any_p & _naive_like(s) & (early > 0.0)
    do_cancel = is_early & (early > NAIVE_IDLE_THRESHOLD_S)
    do_hold = is_early & ~do_cancel
    do_learn = any_p & ~do_cancel

    est = asa.learn_wait_if(s.est, bins, wait, do_learn)

    prev_done = (prev_row >= 0) & (s.status[pc] == DONE)
    resub_t = jnp.where(prev_done, now, jnp.inf)
    tr = s.trace
    if tr is not None:
        tr = obs_trace.append_if(
            tr, do_cancel, kind=obs_trace.EV_CANCEL, t=now, job=row,
            stage=y.astype(jnp.int32), cores=s.cores[row],
            policy=s.policy, step=s.steps)
    return s._replace(
        trace=tr,
        est=est,
        start_pending=pending.at[y].set(False),
        hold=s.hold.at[y].set(jnp.where(do_hold, early, s.hold[y])),
        oh_cs=s.oh_cs
        + jnp.where(do_hold, s.cores[row] * early, 0.0)
        + jnp.where(do_cancel, s.cores[row] * NAIVE_CANCEL_LATENCY_S, 0.0),
        misses=s.misses + is_early.astype(jnp.int32),
        status=s.status.at[row].set(
            jnp.where(do_cancel, CANCELLED, s.status[row])),
        canc_start=s.canc_start.at[y].set(
            jnp.where(do_cancel, s.start[row], s.canc_start[y])),
        start=s.start.at[row].set(
            jnp.where(do_cancel, jnp.inf, s.start[row])),
        end=s.end.at[row].set(
            jnp.where(do_cancel, jnp.inf, s.end[row])),
        submit=s.submit.at[row].set(
            jnp.where(do_cancel, resub_t, s.submit[row])),
        free=s.free + jnp.where(do_cancel, s.cores[row], 0.0),
        # the ONLY remaining repass source: a cancellation changed the
        # machine (cores freed, row possibly resubmitted at this instant)
        # and the scheduler must run again before any further hook fires
        repass=s.repass | do_cancel,
    )


def _chain_hook(s: ScenarioState, now, bins, greedy, params=None,
                rl_mode: str = "sample") -> ScenarioState:
    """Process ONE pending stage admission: live-sample the §3.2 cascade.

    Stage y first admitted at s_y ⇒ (stage 0 only) sample a_0, fix
    E_y = max(s_y + a_y, E_{y-1}) + t_y, sample the successor's a_{y+1}
    from the live estimator and schedule it for max(now, E_y − a_{y+1}).

    ``params`` (a ``repro.rl.policy.PolicyParams`` pytree, or None)
    enables the learned-policy branch: scenarios with policy id 4 draw
    a_0/a_{y+1} from the MLP head over the same wait bins — observations
    and chosen bins are recorded into ``rl_obs``/``rl_act`` (the
    REINFORCE replay buffer) — while ASA scenarios in the same batch keep
    the estimator draw. ``params=None`` (static) elides the branch
    entirely: the pre-RL trace, bit for bit. ``rl_mode`` picks stochastic
    (training) vs argmax (evaluation) actions, statically.
    """
    n = s.status.shape[0]
    pending = s.chain_pending
    any_p = jnp.any(pending)
    y = jnp.argmax(pending)
    row = jnp.clip(s.wf_rows[y], 0, n - 1)

    # stage 0 samples its own wait estimate at submission (later stages
    # were sampled at their predecessor's admission, below)
    need_a0 = any_p & (y == 0)
    prev_row = jnp.where(y > 0, s.wf_rows[jnp.maximum(y - 1, 0)], -1)
    pc = jnp.clip(prev_row, 0, n - 1)
    prev_ee = jnp.where(prev_row < 0, -jnp.inf, s.expected_end[pc])
    succ = s.wf_next[row]
    sc = jnp.clip(succ, 0, n - 1)
    has_succ = any_p & (succ >= 0)

    def cascade_ee(s: ScenarioState, a0):
        """Stage y's settled a_y and expected end E_y for a given a_0.

        `now` IS the admission instant (events never skip a pending
        submit; repass steps hold time still); the stage's own submit
        entry may already have been rewritten by a same-instant naive
        cancel."""
        pw_row = jnp.where(need_a0, a0, s.pred_wait[row])
        return pw_row, jnp.maximum(now + pw_row, prev_ee) + s.duration[row]

    def asa_draws(s: ScenarioState):
        if greedy is True:
            # static greedy: both draws read the same (unchanged) MAP —
            # one argmax serves a0 and a1, and no PRNG is traced at all
            w_map = asa.map_wait(s.est, bins.astype(jnp.float32))
            return (s.est, jnp.where(need_a0, w_map, 0.0),
                    jnp.where(has_succ, w_map, 0.0))
        est, a0 = asa.sample_wait_if(s.est, bins, need_a0, greedy)
        est, a1 = asa.sample_wait_if(est, bins, has_succ, greedy)
        return est, a0, a1

    if params is None:
        est, a0, a1 = asa_draws(s)
    else:
        # trace-time import: repro.rl depends on xsim.grid → xsim.events,
        # so a module-level import here would be a cycle
        from repro.rl import features as rl_features
        from repro.rl import policy as rl_policy

        def rl_draws(s: ScenarioState):
            est = s.est
            if rl_mode == "sample":
                key, k0, k1 = jax.random.split(est.key, 3)
                est = est._replace(key=key)
            obs0 = rl_features.observe(s, y, row, prev_ee, now, bins)
            i0 = (rl_policy.act_greedy(params, obs0)
                  if rl_mode == "greedy"
                  else rl_policy.act_sample(params, obs0, k0))
            i0 = i0.astype(jnp.int32)
            a0 = jnp.where(need_a0, bins[i0], 0.0)
            _, ee = cascade_ee(s, a0)
            obs1 = rl_features.observe(s, y + 1, sc, ee, now, bins)
            i1 = (rl_policy.act_greedy(params, obs1)
                  if rl_mode == "greedy"
                  else rl_policy.act_sample(params, obs1, k1))
            i1 = i1.astype(jnp.int32)
            a1 = jnp.where(has_succ, bins[i1], 0.0)
            return est, a0, a1, obs0, obs1, i0, i1

        def asa_pad(s: ScenarioState):
            est, a0, a1 = asa_draws(s)
            zeros = jnp.zeros(rl_features.N_FEATURES, jnp.float32)
            return est, a0, a1, zeros, zeros, jnp.int32(-1), jnp.int32(-1)

        est, a0, a1, obs0, obs1, i0, i1 = jax.lax.cond(
            s.policy == RL, rl_draws, asa_pad, s)
        rec0 = (s.policy == RL) & need_a0
        rec1 = (s.policy == RL) & has_succ
        y1 = jnp.clip(y + 1, 0, s.wf_rows.shape[0] - 1)
        rl_obs = s.rl_obs.at[y].set(jnp.where(rec0, obs0, s.rl_obs[y]))
        rl_obs = rl_obs.at[y1].set(jnp.where(rec1, obs1, rl_obs[y1]))
        rl_act = s.rl_act.at[y].set(jnp.where(rec0, i0, s.rl_act[y]))
        rl_act = rl_act.at[y1].set(jnp.where(rec1, i1, rl_act[y1]))
        s = s._replace(rl_obs=rl_obs, rl_act=rl_act)

    pw_row, ee = cascade_ee(s, a0)

    pred_wait = s.pred_wait.at[row].set(pw_row)
    pred_wait = pred_wait.at[sc].set(
        jnp.where(has_succ, a1, pred_wait[sc]))
    return s._replace(
        est=est,
        chain_pending=pending.at[y].set(False),
        pred_wait=pred_wait,
        expected_end=s.expected_end.at[row].set(
            jnp.where(any_p, ee, s.expected_end[row])),
        submit=s.submit.at[sc].set(
            jnp.where(has_succ, jnp.maximum(now, ee - a1), s.submit[sc])),
    )


def _drain_hooks(s: ScenarioState, now, bins, greedy, naive: bool,
                 params, rl_mode: str) -> ScenarioState:
    """Drain every same-instant pending stage hook inside this step.

    One (start, chain) pair per iteration — the identical hook-call
    (and therefore PRNG-consumption) order the old one-pair-per-repass-
    step mechanism produced, minus the full scan step (completion scan +
    scheduling pass) each extra pair used to cost. The loop is bounded
    structurally: every iteration clears one ``start_pending`` and/or one
    ``chain_pending`` bit and never sets new ones (pendings are only
    raised at step level, from admissions and starts), so it runs at most
    ``max_stages`` times. A naive/RL cancel sets ``repass`` and exits:
    the machine changed mid-instant and must be rescheduled (a full
    same-time step) before later hooks may fire — matching the previous
    behaviour bit for bit on the cancel paths.
    """
    def cond(s: ScenarioState):
        return (~s.repass) & (jnp.any(s.start_pending)
                              | jnp.any(s.chain_pending))

    def body(s: ScenarioState):
        s = _start_hook(s, now, bins, naive)     # learn (+ naive miss) …
        return _chain_hook(s, now, bins, greedy, params, rl_mode)
        # … then predict, as the event-driven sim does

    with jax.named_scope("xsim.hooks"):
        return jax.lax.while_loop(cond, body, s)


def sim_step(s: ScenarioState, bins, *, bf_passes: int = backfill.BF_PASSES,
             freed_mode: str = "ref", pred_mode: str | None = None,
             naive: bool = True, params=None,
             rl_mode: str = "sample", faults: bool = False) -> ScenarioState:
    """One event step. ``pred_mode`` None reads the per-scenario
    ``pred_greedy`` flag (traced); ``"greedy"``/``"sample"`` stake the
    prediction rule out statically — the greedy fleet hot path then never
    traces the categorical draw. ``naive=False`` asserts (statically) that
    no scenario in the batch runs ASA-Naive (or the learned policy, which
    shares the cancel/resubmit world), eliding that machinery;
    ``grid.run_grid`` sets it from the grid's policy roster. ``params`` /
    ``rl_mode`` feed the learned-policy chain-hook branch (see
    ``_chain_hook``); ``params=None`` elides it. ``faults=False`` asserts
    (statically) that no scenario carries capacity-fault events, eliding
    the fault machinery (``_apply_faults`` + drain-debt collection) —
    ``grid.run_grid`` sets it from the grid's fault schedules.

    The device work is named by phase for the profiler (op metadata
    only; the compiled program is the same): ``xsim.events`` (time
    advance, completions, releases, faults, admissions),
    ``xsim.schedule`` (the scheduling pass, its reservation under
    ``xsim.reserve``) and ``xsim.hooks`` (the ASA start and chain
    hooks)."""
    if rl_mode not in ("sample", "greedy"):
        raise ValueError(f"unknown rl_mode {rl_mode!r}")
    greedy = {None: s.pred_greedy, "greedy": True,
              "sample": False}[pred_mode]
    with jax.named_scope("xsim.events"):
        nxt = next_event_time(s, naive, faults)
        now = jnp.where(jnp.isfinite(nxt), jnp.maximum(nxt, s.t), s.t)
        # utilization integral over (t, now] at the pre-event allocation
        busy_cs = s.busy_cs + (s.total - s.free) * (now - s.t)
        s = s._replace(t=now, busy_cs=busy_cs, repass=jnp.asarray(False),
                       # drained lanes don't count: `steps` is the
                       # events-executed profile signal vs. the n_steps
                       # budget
                       steps=s.steps + jnp.isfinite(nxt).astype(jnp.int32))
        s, newly_done = complete_jobs(s, now, faults)
        s = _release_per_stage(s, newly_done, now)
        resub_fire = resub_succ = None
        if naive:
            s, resub_fire, resub_succ = _release_naive_resubmit(
                s, newly_done, now)
        if faults:
            # after completions (a job ending at the fault instant
            # finished), before admissions/scheduling (which see
            # post-fault capacity)
            s = _apply_faults(s, now)
        s, newly_admitted = admit_jobs(s, now, naive)
        # first admissions of ASA/naive stages queue a chain-hook event
        # (the -inf expected_end sentinel keeps resubmissions from
        # re-firing)
        rows = jnp.clip(s.wf_rows, 0, s.status.shape[0] - 1)
        stage_ok = (s.wf_rows >= 0) & _asa_like(s)
        s = s._replace(chain_pending=s.chain_pending | (
            stage_ok & newly_admitted[rows]
            & jnp.isneginf(s.expected_end[rows])))
    pre_start = s.start
    s = backfill.schedule_pass(s, bf_passes=bf_passes, freed_mode=freed_mode)
    with jax.named_scope("xsim.hooks"):
        started = (s.status == RUNNING) & jnp.isinf(pre_start)
    if s.trace is not None:
        # one fused ring write per step, in event order: finishes,
        # naive resubmissions, admissions, starts (cancels are appended
        # from the start hook itself, inside the drain)
        n = s.status.shape[0]
        row_i = jnp.arange(n, dtype=jnp.int32)
        stg = _job_stage(s)
        segs = [(newly_done, obs_trace.EV_FINISH, row_i, stg, s.cores)]
        if naive:
            segs.append((resub_fire, obs_trace.EV_RESUBMIT, resub_succ,
                         stg[resub_succ], s.cores[resub_succ]))
        segs.append((newly_admitted, obs_trace.EV_SUBMIT, row_i, stg,
                     s.cores))
        segs.append((started, obs_trace.EV_START, row_i, stg, s.cores))
        s = s._replace(trace=obs_trace.append_segments(
            s.trace, segs, t=now, policy=s.policy, step=s.steps))
    with jax.named_scope("xsim.hooks"):
        s = s._replace(start_pending=s.start_pending | (
            stage_ok & started[rows]))
    return _drain_hooks(s, now, bins, greedy, naive, params, rl_mode)


CHUNK_STEPS = 8  # scan-chunk size between drain checks (see `simulate`)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "chunk_steps", "bf_passes",
                                    "freed_mode", "pred_mode", "naive",
                                    "rl_mode", "faults"))
def simulate(s: ScenarioState, *, n_steps: int,
             chunk_steps: int = CHUNK_STEPS,
             bf_passes: int = backfill.BF_PASSES,
             freed_mode: str = "ref", pred_mode: str | None = None,
             naive: bool = True, params=None,
             rl_mode: str = "sample", faults: bool = False) -> ScenarioState:
    """Run up to ~``n_steps`` event steps, stopping early once drained.

    The scan is split into a static ``n_steps % chunk_steps`` remainder
    scan (run first, while there is certainly work) followed by
    ``chunk_steps``-step chunks under an outer ``lax.while_loop`` that
    exits as soon as ``next_event_time`` hits +inf — a drained scenario
    stops paying for dead budget steps, and at most exactly ``n_steps``
    steps ever run. A drained ``sim_step`` is an exact no-op (time,
    PRNG, every table field), so the early exit cannot change the
    result: final states are bit-identical to the unchunked program for
    every chunk size — in the truncation regime too, where both run
    exactly ``n_steps`` steps in the same order — and under
    ``vmap``/``shard_map`` (where the exit condition any-reduces over
    the per-device batch) for every device count. ``chunk_steps=0``
    disables chunking: one static ``n_steps`` scan, the pre-chunking
    program.
    """
    m = s.est.log_p.shape[-1]
    bins = jnp.asarray(make_bins(m), jnp.float32)

    def body(s, _):
        return sim_step(s, bins, bf_passes=bf_passes, freed_mode=freed_mode,
                        pred_mode=pred_mode, naive=naive, params=params,
                        rl_mode=rl_mode, faults=faults), None

    if chunk_steps <= 0:
        s, _ = jax.lax.scan(body, s, None, length=n_steps)
        return s

    n_chunks, rem = divmod(n_steps, chunk_steps)
    if rem:
        s, _ = jax.lax.scan(body, s, None, length=rem)

    def chunk_cond(carry):
        s, i = carry
        return (i < n_chunks) & jnp.isfinite(
            next_event_time(s, naive, faults))

    def chunk_body(carry):
        s, i = carry
        s, _ = jax.lax.scan(body, s, None, length=chunk_steps)
        return s, i + 1

    s, _ = jax.lax.while_loop(chunk_cond, chunk_body, (s, jnp.int32(0)))
    return s


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "chunk_steps", "bf_passes",
                                    "freed_mode", "pred_mode", "naive",
                                    "rl_mode", "faults"))
def sweep(batched: ScenarioState, *, n_steps: int,
          chunk_steps: int = CHUNK_STEPS,
          bf_passes: int = backfill.BF_PASSES,
          freed_mode: str = "ref", pred_mode: str | None = None,
          naive: bool = True, params=None,
          rl_mode: str = "sample", faults: bool = False) -> ScenarioState:
    """The fleet program: vmap(simulate) over a batched ScenarioState.

    ``freed_mode="tpu"`` routes the reservation scan through the Pallas
    kernel (vmap batches it into one (B, N) grid program). ``params``
    (the learned policy head's weights) is closed over, so it broadcasts
    across the fleet rather than being vmapped. The chunked drain exit
    any-reduces over the batch: the sweep stops as soon as EVERY scenario
    is out of events.
    """
    return jax.vmap(
        lambda s: simulate(s, n_steps=n_steps, chunk_steps=chunk_steps,
                           bf_passes=bf_passes, freed_mode=freed_mode,
                           pred_mode=pred_mode, naive=naive, params=params,
                           rl_mode=rl_mode, faults=faults)
    )(batched)


@functools.lru_cache(maxsize=None)
def _sharded_sweep_fn(mesh, n_steps, chunk_steps, bf_passes, freed_mode,
                      pred_mode, naive, rl_mode, faults, with_params):
    """Compiled shard_map(sweep) for one (mesh, static-config) cell.

    Cached so repeated sweeps (warm_fleet rounds, RL iterations, bench
    reps) reuse one jitted program — the same role ``jax.jit``'s own
    cache plays on the vmap path. ``chunk_steps`` is part of the key:
    each chunking choice is its own compiled program (the early-exit
    while_loop structure depends on it).
    """
    from repro.parallel import fleet as pfleet

    spec = pfleet.shard_spec()

    def block(shard: ScenarioState, params):
        return sweep(shard, n_steps=n_steps, chunk_steps=chunk_steps,
                     bf_passes=bf_passes, freed_mode=freed_mode,
                     pred_mode=pred_mode, naive=naive, params=params,
                     rl_mode=rl_mode, faults=faults)

    if with_params:
        fn = jax.shard_map(block, mesh=mesh,
                           in_specs=(spec, pfleet.replicated_spec()),
                           out_specs=spec, check_vma=False)
    else:
        fn = jax.shard_map(lambda shard: block(shard, None), mesh=mesh,
                           in_specs=(spec,), out_specs=spec,
                           check_vma=False)
    return jax.jit(fn)


def sharded_sweep(batched: ScenarioState, *, mesh, n_steps: int,
                  chunk_steps: int = CHUNK_STEPS,
                  bf_passes: int = backfill.BF_PASSES,
                  freed_mode: str = "ref", pred_mode: str | None = None,
                  naive: bool = True, params=None,
                  rl_mode: str = "sample",
                  faults: bool = False) -> ScenarioState:
    """``sweep`` split over the devices of a 1-D ``scenarios`` mesh.

    Each device runs the plain vmapped program on its contiguous block of
    scenarios (``params`` replicated), so the gathered result is
    bit-identical to the single-device ``sweep`` — pinned by
    tests/test_xsim_sharded.py. The chunked drain exit is *per device*
    (each block's while_loop any-reduces over its own lanes): a device
    whose scenarios drain early stops stepping while busier devices run
    on, and because drained steps are exact no-ops the gathered result
    still matches the vmap path bit for bit. Batch sizes not divisible by
    the shard count are padded with copies of scenario 0 (a valid row, so
    the pad lanes run the same control flow) and the pad rows are sliced
    off the gathered output. Build the mesh with
    ``repro.launch.mesh.make_scenarios_mesh``.
    """
    from repro.parallel import fleet as pfleet

    n_shards = mesh.shape[pfleet.SCENARIO_AXIS]
    b = pfleet.batch_size(batched)
    padded, _mask = pfleet.pad_batch(batched, n_shards)
    fn = _sharded_sweep_fn(mesh, n_steps, chunk_steps, bf_passes,
                           freed_mode, pred_mode, naive, rl_mode, faults,
                           params is not None)
    out = fn(padded, params) if params is not None else fn(padded)
    return pfleet.unpad(out, b)
