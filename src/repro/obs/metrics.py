"""Counters/histograms registry over finished sweeps.

``scenario_summary`` reduces ONE final ``ScenarioState`` to a flat dict
of counters (event steps vs budget, drain flag, naive misses/cancels,
backfill hits, over-allocation core-hours, trace event counts) plus a
wait-time histogram over the §4.5 bins. ``sweep_summary`` vmaps it and
reduces the batch axis on device; ``sharded_sweep_summary`` runs the
same reduction *inside* a ``shard_map`` block with a ``psum`` over the
1-D ``scenarios`` mesh, weighting by the padding-validity mask so the
row-0 pad copies never double-count — fleet-level metrics leave the mesh
already reduced to a handful of scalars.

Counter columns are integer sums, so the sharded reduction is exactly
the vmap reduction (integer addition is associative); the few float
columns (``oh_core_hours``, ``steps_frac``) match to reduction order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bins import M_DEFAULT, make_bins
from repro.obs import trace as obtrace
from repro.xsim.state import DONE, QUEUED, ScenarioState

# histogram domain: the same m=53 wait alternatives ASA discretizes over
HIST_BINS = M_DEFAULT


def wait_histogram(s: ScenarioState, bins: jax.Array) -> jax.Array:
    """(M,) i32 counts of observed stage waits, log-nearest-bin bucketed.

    Buckets exactly like ``core.bins.nearest_bin`` (argmin in log space),
    over the workflow rows that actually started.
    """
    valid = s.is_wf & jnp.isfinite(s.start)
    w = jnp.maximum(s.start - s.submit, 1e-9)
    d = jnp.abs(jnp.log(bins)[None, :] - jnp.log(w)[:, None])
    idx = jnp.argmin(d, axis=-1)
    return jnp.zeros(bins.shape[0], jnp.int32).at[idx].add(
        valid.astype(jnp.int32))


def backfill_hits(s: ScenarioState) -> jax.Array:
    """i32 count of FCFS overtakes: job i started while an
    earlier-submitted job j was still waiting (j submitted before i,
    already in the queue at i's start, started later) — each such i is
    one backfill placement the sorted-reservation pass admitted early."""
    started = jnp.isfinite(s.start) & (s.status != QUEUED)
    live = s.cores > 0.0
    overtaken = (live[None, :] & (s.submit[None, :] < s.submit[:, None])
                 & (s.submit[None, :] <= s.start[:, None])
                 & (s.start[None, :] > s.start[:, None]))
    hit = started & live & jnp.any(overtaken, axis=1)
    return jnp.sum(hit.astype(jnp.int32))


def scenario_summary(s: ScenarioState, n_steps: int) -> dict[str, jax.Array]:
    """Per-scenario observability counters (vmap for a fleet).

    ``n_steps`` is the sweep's static step budget (``XSimConfig.n_steps``)
    — ``drained`` means the scenario ran out of events before the budget
    ran out of steps. Trace-derived columns appear only when the state
    carries a trace buffer (``s.trace is None`` elides them statically).
    """
    bins = jnp.asarray(make_bins(HIST_BINS), jnp.float32)
    wf = s.is_wf
    out = {
        "steps": s.steps,
        "step_budget": jnp.int32(n_steps),
        "drained": (s.steps < n_steps).astype(jnp.int32),
        "wf_done": jnp.sum((wf & (s.status == DONE)).astype(jnp.int32)),
        "wf_total": jnp.sum(wf.astype(jnp.int32)),
        "misses": s.misses,
        "cancels": jnp.sum(jnp.isfinite(s.canc_start).astype(jnp.int32)),
        "holds": jnp.sum((s.hold > 0.0).astype(jnp.int32)),
        "oh_core_hours": s.oh_cs / 3600.0,
        "backfill_hits": backfill_hits(s),
        "wait_hist": wait_histogram(s, bins),
    }
    if s.trace is not None:
        C = s.trace.data.shape[-2]
        out["trace_events"] = s.trace.head
        out["trace_dropped"] = jnp.maximum(s.trace.head - C, 0)
        out["trace_overflowed"] = obtrace.overflowed(s.trace).astype(
            jnp.int32)
        kinds = obtrace.column(s.trace, "kind")
        for ev, name in obtrace.EVENT_NAMES.items():
            # surviving (post-overflow) events per kind
            out[f"ev_{name}"] = jnp.sum((kinds == ev).astype(jnp.int32))
    return out


def _reduce(per: dict[str, jax.Array], weights: jax.Array,
            n_steps: int) -> dict[str, jax.Array]:
    """Batch-axis reduction of vmapped summaries (weights mask pad rows)."""
    out = {}
    for k, v in per.items():
        w = weights.reshape((-1,) + (1,) * (v.ndim - 1)).astype(v.dtype)
        out[k] = jnp.sum(v * w, axis=0)
    n = jnp.sum(weights.astype(jnp.float32))
    out["n_scenarios"] = n.astype(jnp.int32)
    out["step_budget"] = jnp.int32(n_steps)
    out["drain_frac"] = out.pop("drained").astype(jnp.float32) \
        / jnp.maximum(n, 1.0)
    out["steps_frac"] = out["steps"].astype(jnp.float32) \
        / jnp.maximum(n * n_steps, 1.0)
    return out


@functools.partial(jax.jit, static_argnames=("n_steps",))
def sweep_summary(final: ScenarioState, *, n_steps: int
                  ) -> dict[str, jax.Array]:
    """Fleet-level summary of a batched final state (single device)."""
    per = jax.vmap(lambda s: scenario_summary(s, n_steps))(final)
    B = per["steps"].shape[0]
    return _reduce(per, jnp.ones(B, jnp.int32), n_steps)


def sharded_sweep_summary(final: ScenarioState, mesh, *, n_steps: int
                          ) -> dict[str, jax.Array]:
    """``sweep_summary`` without gathering the states: each device
    reduces its own block of final scenarios and a ``psum`` over the
    ``scenarios`` mesh axis finishes the job — only the summary scalars
    (and one (53,) histogram) ever leave the mesh. Pad rows (copies of
    scenario 0, see ``parallel.fleet.pad_batch``) are zero-weighted so
    they never double-count. Counter columns match ``sweep_summary``
    exactly (integer sums); float columns to reduction order."""
    from repro.parallel import fleet as pfleet

    n_shards = mesh.shape[pfleet.SCENARIO_AXIS]
    padded, mask = pfleet.pad_batch(final, n_shards)

    def block(shard: ScenarioState, m):
        per = jax.vmap(lambda s: scenario_summary(s, n_steps))(shard)
        local = _reduce(per, m, n_steps)
        # undo _reduce's local normalizations, psum the raw sums, redo
        n_loc = local.pop("n_scenarios")
        drained = local.pop("drain_frac") * jnp.maximum(
            n_loc.astype(jnp.float32), 1.0)
        local.pop("steps_frac")
        summed = jax.lax.psum(
            {**local, "n_scenarios": n_loc, "drained": drained},
            pfleet.SCENARIO_AXIS)
        n = summed.pop("n_scenarios").astype(jnp.float32)
        summed["n_scenarios"] = n.astype(jnp.int32)
        summed["step_budget"] = jnp.int32(n_steps)
        summed["drain_frac"] = summed.pop("drained") / jnp.maximum(n, 1.0)
        summed["steps_frac"] = summed["steps"].astype(jnp.float32) \
            / jnp.maximum(n * n_steps, 1.0)
        return summed

    spec = pfleet.shard_spec()
    fn = jax.shard_map(block, mesh=mesh,
                       in_specs=(spec, spec),
                       out_specs=pfleet.replicated_spec(), check_vma=False)
    return jax.jit(fn)(padded, mask)


def replay_chain_waits(s: ScenarioState
                       ) -> tuple[np.ndarray, np.ndarray, np.float32]:
    """Reconstruct the ASA-chain perceived stage waits from the trace.

    Replays ONE scenario's decoded event ring (submit/start/cancel
    order) through the same f32 recurrences ``events._start_hook`` and
    ``compare.metrics`` use — predecessor logical end
    ``start + hold + duration``, naive hold-vs-cancel rule, then the
    settled-timeline chain ``le_y = max(start_y + hold_y, le_{y-1}) +
    t_y`` — using only trace timestamps plus the static job table
    (durations, stage chain). Returns ``(pwt, valid, twt)``: per-stage
    perceived waits, their validity mask, and their f32 running sum —
    bit-equal to ``compare.metrics(s)["twt_s"]`` for ASA-like scenarios
    (the differential test in tests/test_obs.py pins this on the 12
    mirrored QueueSim scenarios).
    """
    from repro.sched.strategies import NAIVE_IDLE_THRESHOLD_S
    from repro.xsim.state import ASA_NAIVE, RL

    if s.trace is None:
        raise ValueError("scenario carries no trace buffer")
    events, meta = obtrace.decode(s.trace)
    if meta["dropped"]:
        raise ValueError(f"ring overflowed ({meta['dropped']} events "
                         "dropped); waits are not reconstructible")
    # the miss machinery only runs for dependency-free policies
    # (events._naive_like); other policies take every start as settled
    naive_like = int(np.asarray(s.policy)) in (ASA_NAIVE, RL)
    wf_rows = np.asarray(s.wf_rows)
    dur = np.asarray(s.duration, np.float32)
    S = wf_rows.shape[0]
    stage_of = {int(r): y for y, r in enumerate(wf_rows) if r >= 0}
    f32 = np.float32
    start = np.full(S, np.inf, f32)
    hold = np.zeros(S, f32)
    canc = np.full(S, np.inf, f32)
    cancelled = np.zeros(S, bool)
    submit0 = f32(np.nan)
    thr = f32(NAIVE_IDLE_THRESHOLD_S)

    for i in range(len(events["kind"])):
        r = int(events["job"][i])
        if r not in stage_of:
            continue
        k = int(events["kind"][i])
        y = stage_of[r]
        t = f32(events["t"][i])
        if k == obtrace.EV_SUBMIT and y == 0 and np.isnan(submit0):
            submit0 = t
        elif k == obtrace.EV_START:
            if y == 0 or not naive_like:
                start[y] = t
                continue
            yp, rp = y - 1, int(wf_rows[y - 1])
            # _start_hook's prev_logical, f32 op for op
            if np.isfinite(start[yp]):
                prev_logical = f32(f32(start[yp] + hold[yp]) + dur[rp])
            elif cancelled[yp] and np.isfinite(canc[yp]):
                prev_logical = f32(canc[yp] + dur[rp])
            else:
                prev_logical = f32(np.inf)
            early = f32(prev_logical - t)
            if early > thr:         # long gap: cancelled at this instant
                cancelled[y] = True  # (EV_CANCEL follows in the ring)
                canc[y] = t
            else:
                start[y] = t
                cancelled[y] = False
                if early > f32(0.0):
                    hold[y] = early

    # compare.metrics' settled-timeline chain, f32 op for op
    le = f32(-np.inf)
    twt = f32(0.0)
    pwt = np.zeros(S, f32)
    valid = np.zeros(S, bool)
    for y in range(S):
        r = int(wf_rows[y])
        if r < 0 or not np.isfinite(start[y]):
            continue
        valid[y] = True
        start_l = f32(start[y] + hold[y])
        if y == 0:
            pwt[y] = f32(start[y] - submit0)
            le = f32(start_l + dur[r])
        else:
            pwt[y] = (f32(0.0) if np.isneginf(le)
                      else np.maximum(f32(start[y] - le), f32(0.0)))
            le = f32(np.maximum(start_l, le) + dur[r])
        twt = f32(twt + pwt[y])
    return pwt, valid, twt


def to_host(summary: dict[str, jax.Array]) -> dict:
    """JSON-safe python view of a (fleet or per-scenario) summary."""
    out = {}
    for k, v in summary.items():
        a = np.asarray(v)
        if a.ndim == 0:
            out[k] = a.item()
        else:
            out[k] = a.tolist()
    return out
