"""Plain reference of one xsim sweep call: the scenario tables made from
the seed, each scenario simulated event by event in numpy float32, and
the workflow's total wait (TWT) and makespan.

Semantics (``repro.xsim`` documents the same model): a slotted job table
(warm running jobs, a queued backlog, Poisson-burst arrivals, the
workflow's stage rows); at each event time, completions, then Per-Stage
successor releases, admissions, one FCFS + EASY-backfill pass with at
most ``BF_PASSES`` backfill starts, then ASA's start hook (learn the
observed wait) and chain hook (MAP wait, expected end, successor submit)
for each pending stage, lowest first, alternating. Policies 0 BigJob,
1 Per-Stage and 2 ASA only; the fleet reads the live MAP (greedy).

The tables come from JAX's PRNG on the device, built by a jitted batch
of the same shape as the program's, since a table that differs in one
rounding sends the whole event sequence elsewhere. Every time is then
float32 arithmetic, which numpy rounds as the device does.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.alg1 import BINS, Alg1

BIGJOB, PER_STAGE, ASA = 0, 1, 2
INVALID, PENDING, QUEUED, RUNNING, DONE = 0, 1, 2, 3, 4
BF_PASSES = 16
F32 = np.float32
INF = F32(np.inf)


# ------------------------------------------------------------ the tables
def stage_rows(workflow: dict, scale: int, max_stages: int, seq_cores: int,
               base_cores: int):
    """(cores, durations, valid) of a workflow at a core scale."""
    c = np.zeros(max_stages, np.float32)
    d = np.zeros(max_stages, np.float32)
    v = np.zeros(max_stages, bool)
    for y, st in enumerate(workflow["stages"]):
        par = st["parallel"]
        c[y] = scale if par else seq_cores
        d[y] = (st["base_t"] * (base_cores / scale) ** st["alpha"]
                if par else st["base_t"])
        v[y] = True
    return c, d, v


def _one_table(key, center, wf_c, wf_d, wf_v, policy, sizes, t0):
    """One scenario's job table (jnp; vmapped over the batch)."""
    n_warm, n_back, n_arr, n_st = sizes
    (total, rate, cmean, csig, dmean, dsig, backlog, burst_mean) = center
    ks = jax.random.split(key, 9)

    def widths(k, n):
        w = jnp.exp(cmean + csig * jax.random.normal(k, (n,)))
        return jnp.clip(jnp.round(w), 1.0, jnp.maximum(total // 2, 1.0))

    def durations(k, n):
        d = jnp.exp(dmean + dsig * jax.random.normal(k, (n,)))
        return jnp.clip(d, 30.0, 7.0 * 86400.0)

    wc = widths(ks[0], n_warm)
    wd = durations(ks[1], n_warm)
    w_ok = jnp.cumsum(wc) <= 0.97 * total
    wc = jnp.where(w_ok, wc, 0.0)
    w_end = jax.random.uniform(ks[2], (n_warm,), minval=0.05,
                               maxval=1.0) * wd
    free = total - jnp.sum(wc)
    bc = widths(ks[3], n_back)
    bd = durations(ks[4], n_back)
    b_ok = jnp.arange(n_back) < backlog
    gaps = jax.random.exponential(ks[5], (n_arr,)) / rate
    group_t = jnp.cumsum(gaps)
    u = jax.random.uniform(ks[6], (n_arr,), minval=1e-6, maxval=1.0 - 1e-6)
    p_burst = 1.0 / jnp.maximum(burst_mean, 1.0)
    burst = jnp.where(burst_mean <= 1.0, 1.0,
                      jnp.floor(jnp.log(u) / jnp.log1p(-p_burst)) + 1.0)
    group_of = jnp.searchsorted(jnp.cumsum(burst), jnp.arange(n_arr),
                                side="right")
    a_submit = group_t[jnp.clip(group_of, 0, n_arr - 1)]
    ac = widths(ks[7], n_arr)
    ad = durations(ks[8], n_arr)
    a_ok = a_submit <= 10 * 86400.0

    y = jnp.arange(n_st)
    big = policy == BIGJOB
    f_valid = jnp.where(big, y == 0, wf_v)
    f_cores = jnp.where(big, jnp.where(y == 0, jnp.max(wf_c), 0.0), wf_c)
    total_dur = jnp.sum(jnp.where(wf_v, wf_d, 0.0))
    f_durs = jnp.where(big, jnp.where(y == 0, total_dur, 0.0), wf_d)
    inf = jnp.inf
    submit = jnp.concatenate([
        jnp.zeros(n_warm), jnp.zeros(n_back), jnp.where(a_ok, a_submit, inf),
        jnp.where(y == 0, t0, inf)])
    cores = jnp.concatenate([wc, jnp.where(b_ok, bc, 0.0),
                             jnp.where(a_ok, ac, 0.0), f_cores])
    dur = jnp.concatenate([wd, bd, ad, f_durs])
    end = jnp.concatenate([jnp.where(w_ok, w_end, inf),
                           jnp.full(n_back + n_arr + n_st, inf)])
    status = jnp.concatenate([
        jnp.where(w_ok, RUNNING, INVALID), jnp.where(b_ok, QUEUED, INVALID),
        jnp.where(a_ok, PENDING, INVALID),
        jnp.where(f_valid, PENDING, INVALID)]).astype(jnp.int32)
    return submit, cores, dur, end, status, f_valid, free


_tables = jax.jit(jax.vmap(_one_table, in_axes=(0,) * 6 + (None, None)),
                  static_argnums=(6, 7))


@dataclass
class Scenario:
    """One scenario as the reference runs it (host numpy)."""

    policy: int
    submit: np.ndarray
    cores: np.ndarray
    dur: np.ndarray
    end: np.ndarray
    status: np.ndarray
    wf_valid: np.ndarray
    free: np.float32
    t0: np.float32
    est_key: object = None


def center_row(center: dict, shrink: float) -> tuple:
    c = center
    return (np.float32(max(c["nodes"] * c["cores_per_node"] * shrink, 8.0)),
            np.float32(c["bg_arrival_rate"] * shrink),
            np.float32(c["bg_cores_mean"]), np.float32(c["bg_cores_sigma"]),
            np.float32(c["bg_duration_mean_s"]),
            np.float32(c["bg_duration_sigma"]),
            np.float32(max(round(c["bg_initial_backlog"] * shrink), 1)),
            np.float32(c["bg_burst_mean"]))


def make_scenarios(cfg: dict, workflow: str, n_seeds: int, grid_seed: int,
                   device=None) -> list[Scenario]:
    """The scenarios of one sweep call, in the grid's order: scale, then
    policy, then seed. Background keys are
    ``fold_in(PRNGKey(grid_seed), geometry * 100003 + seed)``; each
    scenario's estimator key is ``fold_in(split(PRNGKey(0), n_geo)[geo],
    index + 100003)`` (sweep seed 1)."""
    x = cfg["xsim"]
    shrink = cfg["shrink"]
    center = cfg["center"]
    scales = [max(int(round(s * shrink)), 2) for s in center["scales"]]
    wf = cfg["workflows"][workflow]
    rows, policies, keys, geo = [], [], [], []
    base = jax.random.PRNGKey(grid_seed)
    for g, scale in enumerate(scales):
        c, d, v = stage_rows(wf, scale, x["max_stages"], cfg["seq_cores"],
                             cfg["base_cores"])
        for pol in cfg["policies"]:
            for s in range(n_seeds):
                rows.append((c, d, v, pol))
                policies.append(pol)
                keys.append(jax.random.fold_in(base, g * 100_003 + s))
                geo.append(g)
    crow = center_row(center, shrink)
    b = len(rows)
    dev = device or jax.devices()[0]
    args = jax.device_put((
        jnp.stack(keys), tuple(jnp.full((b,), v, jnp.float32) for v in crow),
        jnp.asarray(np.stack([r[0] for r in rows])),
        jnp.asarray(np.stack([r[1] for r in rows])),
        jnp.asarray(np.stack([r[2] for r in rows])),
        jnp.asarray(np.array([r[3] for r in rows], np.int32))), dev)
    sizes = (x["n_warm"], x["n_backlog"], x["n_arrivals"], x["max_stages"])
    out = jax.device_get(_tables(*args, sizes, np.float32(x["t0"])))
    fleet = jax.random.split(jax.random.PRNGKey(0), len(scales))
    est_keys = [jax.random.fold_in(fleet[g], np.uint32(i + 100_003))
                for i, g in enumerate(geo)]
    sub, cores, dur, end, status, valid, free = (np.asarray(a) for a in out)
    return [Scenario(policy=pol, submit=sub[i].copy(),
                     cores=cores[i].copy(), dur=dur[i].copy(),
                     end=end[i].copy(), status=status[i].copy(),
                     wf_valid=valid[i].copy(), free=F32(free[i]),
                     t0=F32(x["t0"]), est_key=est_keys[i])
            for i, pol in enumerate(policies)]


# ------------------------------------------------------------ simulation
@dataclass
class Result:
    start: np.ndarray
    end: np.ndarray
    status: np.ndarray
    steps: int
    twt: float
    makespan: float
    t_scale: float          # latest workflow end: the scale of its times
    map_outside_ties: int   # MAP reads the program answered off the tie set
    map_ties: int           # MAP reads that rounding decides
    ties: list              # eq.-(3) bins and draws near a tie (alg1)


def _gumbel_draw():
    cpu = jax.devices("cpu")[0]

    @jax.jit
    def draw(key):
        key, sub = jax.random.split(key)
        return key, jax.random.gumbel(sub, (BINS.shape[0],), jnp.float32)

    def f(key):
        with jax.default_device(cpu):
            key, g = draw(jax.device_put(key, cpu))
        return key, np.asarray(g)
    return f


_DRAW = None


def simulate(sc: Scenario, n_steps: int, program_pred_wait=None,
             rnd=None, picks: tuple = ()) -> Result:
    """Run one scenario to its end (or the step budget).

    ``program_pred_wait`` is the program's final ``pred_wait`` row of
    this scenario: where the reference's MAP is a tie that rounding
    decides, the program's choice is taken if it is one of the tied
    bins. ``rnd`` rounds every computed time (the lower-precision
    control); None keeps float32. ``picks`` resolves the estimator's
    choices near a tie (``alg1.best_branch``)."""
    global _DRAW
    r = rnd or (lambda v: v)
    n = sc.submit.shape[0]
    n_st = sc.wf_valid.shape[0]
    submit = r(sc.submit.copy())
    cores = sc.cores.copy()
    dur = r(sc.dur.copy())
    end = r(sc.end.copy())
    start = np.where(sc.status == RUNNING, F32(0.0), INF).astype(F32)
    status = sc.status.copy()
    off = n - n_st
    wf_rows = np.where(sc.wf_valid, off + np.arange(n_st), -1)
    is_asa = sc.policy == ASA
    dep = np.full(n, -1)
    nxt = np.full(n, -1)
    if sc.policy != BIGJOB:
        for y in range(n_st):
            if not sc.wf_valid[y]:
                continue
            if y + 1 < n_st and sc.wf_valid[y + 1]:
                nxt[off + y] = off + y + 1
            if y > 0:
                dep[off + y] = off + y - 1
    pred = np.zeros(n, F32)
    ee = np.full(n, -np.inf, F32)
    est = None
    if is_asa:
        if _DRAW is None:
            _DRAW = _gumbel_draw()
        est = Alg1(sc.est_key, picks)
    free = F32(sc.free)
    t = F32(0.0)
    steps = 0
    outside = ties = 0
    chain_p: set[int] = set()
    start_p: set[int] = set()
    stage_of = {int(wf_rows[y]): y for y in range(n_st) if wf_rows[y] >= 0}

    def start_rows(rows, now):
        nonlocal free
        status[rows] = RUNNING
        start[rows] = now
        end[rows] = r(now + dur[rows])
        free = F32(free - cores[rows].sum(dtype=F32))

    def schedule(now) -> np.ndarray:
        nonlocal free
        before = status == RUNNING
        dep_ok = np.ones(n, bool)
        has = dep >= 0
        dep_ok[has] = status[dep[has]] == DONE
        idx = np.flatnonzero((status == QUEUED) & dep_ok)
        if idx.size == 0:
            return np.zeros(n, bool)
        order = idx[np.argsort(submit[idx], kind="stable")]
        csum = np.cumsum(cores[order], dtype=F32)
        k = int(np.count_nonzero(csum <= free))
        if k:
            start_rows(order[:k], now)
        rest = order[k:]
        if rest.size:
            head = rest[0]
            run = np.flatnonzero(status == RUNNING)
            shadow, extra = INF, F32(0.0)
            if run.size:
                so = run[np.argsort(end[run], kind="stable")]
                es = end[so]
                cs = np.cumsum(cores[so], dtype=F32)
                last = np.searchsorted(es, es, side="right") - 1
                freed = cs[last]
                ok = free + freed >= cores[head]
                if ok.any():
                    j = int(np.argmax(ok))
                    shadow = es[j]
                    extra = F32(free + freed[j] - cores[head])
            cand = rest[1:]
            cc = cores[cand]
            in_time = r(now + dur[cand]) <= shadow
            pos = 0
            for _ in range(BF_PASSES):
                m = (cc[pos:] <= free) & (in_time[pos:] | (cc[pos:] <= extra))
                if not m.any():
                    break
                j = pos + int(np.argmax(m))
                if cc[j] <= extra:
                    extra = F32(extra - cc[j])
                start_rows(cand[j:j + 1], now)
                pos = j + 1
        return (status == RUNNING) & ~before

    def map_wait(row_for_choice) -> F32:
        nonlocal outside, ties
        choices = est.map_choices()
        pick = choices[0]
        ties += len(choices) > 1
        if len(choices) > 1 and program_pred_wait is not None:
            got = program_pred_wait[row_for_choice]
            hit = [i for i in choices if BINS[i] == got]
            if hit:
                pick = hit[0]
            else:
                outside += 1
        return BINS[pick]

    while steps < n_steps:
        pend = status == PENDING
        run = status == RUNNING
        nx = min(submit[pend].min(initial=INF), end[run].min(initial=INF))
        if not np.isfinite(nx):
            break
        now = F32(max(nx, t))
        t = now
        steps += 1
        done = run & (end <= now)
        free = F32(free + cores[done].sum(dtype=F32))
        status[done] = DONE
        if sc.policy == PER_STAGE:
            for row in np.flatnonzero(done & (nxt >= 0)):
                submit[nxt[row]] = now
        adm = (status == PENDING) & (submit <= now)
        status[adm] = QUEUED
        if is_asa:
            for row in np.flatnonzero(adm):
                y = stage_of.get(int(row))
                if y is not None and np.isneginf(ee[row]):
                    chain_p.add(y)
        started = schedule(now)
        if is_asa:
            for row in np.flatnonzero(started):
                y = stage_of.get(int(row))
                if y is not None:
                    start_p.add(y)
            while start_p or chain_p:
                if start_p:
                    y = min(start_p)
                    start_p.discard(y)
                    row = wf_rows[y]
                    est.key, g = _DRAW(est.key)
                    est.learn(F32(now - submit[row]), g)
                if chain_p:
                    y = min(chain_p)
                    chain_p.discard(y)
                    row = wf_rows[y]
                    succ = nxt[row]
                    prev_ee = ee[wf_rows[y - 1]] if y > 0 else -INF
                    w = F32(0.0)
                    if y == 0 or succ >= 0:
                        w = map_wait(succ if succ >= 0 else row)
                    pw = w if y == 0 else pred[row]
                    e_y = r(F32(max(r(F32(now + pw)), prev_ee) + dur[row]))
                    pred[row] = pw
                    if succ >= 0:
                        pred[succ] = w
                        submit[succ] = max(now, r(F32(e_y - w)))
                    ee[row] = e_y
    twt, mk = metrics(sc, start, submit, end, dur, wf_rows, is_asa)
    ends = end[wf_rows[wf_rows >= 0]]
    ends = ends[np.isfinite(ends)]
    return Result(start=start, end=end, status=status, steps=steps,
                  twt=twt, makespan=mk,
                  t_scale=float(np.abs(ends).max()) if ends.size else 1.0,
                  map_outside_ties=outside, map_ties=ties,
                  ties=est.ties if est is not None else [])


def metrics(sc, start, submit, end, dur, wf_rows, is_asa):
    """TWT and makespan, float64: Per-Stage and BigJob sum the stage
    waits and take the last end; ASA counts the perceived waits along the
    stage chain (the part of each wait not hidden behind the predecessor's
    end) and its logical end."""
    rows = wf_rows[wf_rows >= 0]
    st = start[rows].astype(np.float64)
    if not is_asa:
        twt = float((st - submit[rows].astype(np.float64)).sum())
        return twt, float(end[rows].astype(np.float64).max() - sc.t0)
    le, twt = -np.inf, 0.0
    for y, row in enumerate(rows):
        s = float(start[row])
        if not np.isfinite(s):
            continue
        if y == 0:
            twt += s - float(submit[row])
            le = s + float(dur[row])
        else:
            twt += 0.0 if np.isneginf(le) else max(s - le, 0.0)
            le = max(s, le) + float(dur[row])
    return twt, le - float(sc.t0)
