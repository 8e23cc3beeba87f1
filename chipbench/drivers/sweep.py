"""The what-if sweep: ``make_grid`` then ``run_grid``, as users call them.

Set-up builds the first call's grid and runs ``run_grid`` once with the
scenarios' events removed (every row INVALID), which compiles and loads
every program of the call at its real shapes and runs no event. The
window then runs whole calls back to back and closes at the end of the
call that crosses ``--seconds``. Afterwards one call, drawn from the
seed, is checked against the reference scenario by scenario.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from chipbench.common import Checks, CompileWatch, rng
from chipbench.generator import sweep_call

# limits of the comparison, from the readings in PERF.md
LIMIT_METRIC_REL_GAP = 1e-5


def _program_cfg(cfg: dict):
    from repro.xsim.grid import XSimConfig

    x = cfg["xsim"]
    return XSimConfig(n_warm=x["n_warm"], n_backlog=x["n_backlog"],
                      n_arrivals=x["n_arrivals"], max_stages=x["max_stages"],
                      t0=x["t0"], pred_mode=x["pred_mode"])


def _check_config(cfg: dict) -> None:
    """The configuration file must describe the center and workflows the
    program runs, since the reference reads them from the file."""
    from repro.sched.centers import CENTERS
    from repro.sched.workflows import BASE_CORES, SEQ_CORES, WORKFLOWS

    c = cfg["center"]
    prog = CENTERS[c["name"]]
    have = {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in vars(prog).items()}
    want = {k: v for k, v in c.items()}
    for name, wf in cfg["workflows"].items():
        have[name] = [dict(name=s.name, parallel=s.parallel, base_t=s.base_t,
                           alpha=s.alpha) for s in WORKFLOWS[name].stages]
        want[name] = wf["stages"]
    have.update(seq_cores=SEQ_CORES, base_cores=BASE_CORES,
                step_budget=_program_cfg(cfg).n_steps)
    want.update(seq_cores=cfg["seq_cores"], base_cores=cfg["base_cores"],
                step_budget=cfg["xsim"]["step_budget"])
    bad = [k for k in want if want[k] != have.get(k)]
    if bad:
        raise SystemExit(f"configuration differs from the program in {bad}")


def make_grid(cfg, traffic, call):
    from repro.xsim.grid import make_grid as program_make_grid

    return program_make_grid(
        _program_cfg(cfg), center_names=(cfg["center"]["name"],),
        workflows=(call.workflow,), policy_ids=tuple(cfg["policies"]),
        n_seeds=traffic["seeds_per_call"], shrink=cfg["shrink"],
        seed=call.grid_seed)


def run_call(grid, traffic):
    from repro.xsim.grid import run_grid

    if traffic.get("n_shards"):
        return run_grid(grid, n_shards=traffic["n_shards"])
    return run_grid(grid)


def _warm(grid, traffic) -> None:
    """Run the call's programs once on tables with no events."""
    import jax
    import jax.numpy as jnp

    from repro.xsim import events

    name = "sharded_sweep" if traffic.get("n_shards") else "sweep"
    real = getattr(events, name)

    def no_events(states, **kw):
        return real(states._replace(status=jnp.zeros_like(states.status)),
                    **kw)

    setattr(events, name, no_events)
    try:
        jax.block_until_ready(run_call(grid, traffic))
    finally:
        setattr(events, name, real)


def run(cell, cfg, traffic, seed, seconds, trace, devices, setup_t0,
        tracer):
    import jax

    _check_config(cfg)
    with tracer.annotate("chipbench.build"):
        first = make_grid(cfg, traffic, sweep_call(traffic, seed, 0))
    _warm(first, traffic)
    setup_s = time.perf_counter() - setup_t0

    calls, kept = [], []
    traced = tracer.start() if trace else False
    watch = CompileWatch().__enter__()
    t_open = time.perf_counter()
    k = 0
    while True:
        call = sweep_call(traffic, seed, k)
        with tracer.annotate("chipbench.build"):
            grid = make_grid(cfg, traffic, call)
        with tracer.annotate("chipbench.run_grid"):
            final, m = run_call(grid, traffic)
            jax.block_until_ready((final, m))
        with tracer.annotate("chipbench.readback"):
            steps = np.asarray(final.steps)
        calls.append({"workflow": call.workflow, "lanes": grid.n,
                      "steps": steps,
                      "wf_done": int(np.asarray(m["wf_done"]).sum()),
                      "wf_total": int(np.asarray(m["wf_total"]).sum())})
        kept.append((call, final, m))
        if traced and k + 1 >= traffic.get("trace_calls", 1):
            tracer.stop()
            traced = False
        k += 1
        if time.perf_counter() - t_open >= seconds:
            break
    window_s = time.perf_counter() - t_open
    watch.__exit__()
    if traced:
        tracer.stop()

    n_scen = sum(c["lanes"] for c in calls)
    failed = sum(int(c["wf_done"] < c["wf_total"]) for c in calls)
    e2e = {"sweep_scenarios_per_s": n_scen / window_s}
    ctx = {"sweep_calls": calls,
           "traced_calls": calls[:traffic.get("trace_calls", 1)]}

    def check(peak_read):
        peak_read()
        j = int(rng(seed, 4).integers(0, len(kept)))
        call, final, m = kept[j]
        host = jax.device_get({
            "start": final.start, "end": final.end, "status": final.status,
            "steps": final.steps, "pred_wait": final.pred_wait,
            "twt": m["twt_s"], "makespan": m["makespan_s"]})
        kept.clear()
        del final, m
        return compare(cfg, traffic, call, host, seed, devices)

    return {"setup_s": setup_s, "e2e": e2e, "ctx": ctx,
            "attempted": n_scen, "failed": failed, "check": check}


def compare(cfg, traffic, call, host, seed, devices) -> Checks:
    """Every checked scenario of the call against the reference."""
    from chipbench.reference import xsim as ref
    from chipbench.reference.alg1 import best_branch

    t0 = time.perf_counter()
    scen = ref.make_scenarios(cfg, call.workflow, traffic["seeds_per_call"],
                              call.grid_seed, device=devices[0])
    n = len(scen)
    pick = np.arange(n)
    want = traffic.get("check_scenarios", n)
    if want < n:
        pick = np.sort(rng(seed, 5).choice(n, size=want, replace=False))
    n_steps = cfg["xsim"]["step_budget"]
    rows = steps = outside = ties = 0
    twt_gap = mk_gap = 0.0
    followed = 0
    for i in pick:
        pw = host["pred_wait"]

        def sim(picks):
            return ref.simulate(scen[i], n_steps, picks=picks,
                                program_pred_wait=None if pw is None
                                else pw[i])

        def gaps(r):
            diff = ((host["start"][i] != r.start) | (host["end"][i] != r.end)
                    | (host["status"][i] != r.status))
            # float32 sums of times of this size round by their ULPs
            scale = max(abs(r.twt), abs(r.makespan), r.t_scale, 1.0)
            return (int(diff.sum()), int(int(host["steps"][i]) != r.steps),
                    r.map_outside_ties,
                    abs(float(host["twt"][i]) - r.twt) / scale,
                    abs(float(host["makespan"][i]) - r.makespan) / scale)

        def score(r):
            g = gaps(r)
            return g[:3] + (max(g[3:]),)

        r, picks = best_branch(sim, score, cap=16)
        g = gaps(r)
        rows += g[0]
        steps += g[1]
        outside += g[2]
        twt_gap, mk_gap = max(twt_gap, g[3]), max(mk_gap, g[4])
        ties += r.map_ties + len(r.ties)
        followed += sum(p != 0 for p in picks)
    print(f"reference: {len(pick)} of {n} scenarios of call {call.index} "
          f"({call.workflow}) in {time.perf_counter() - t0:.1f}s; "
          f"{ties} MAP reads and estimator choices near a tie, "
          f"{followed} of the latter followed to the other side",
          file=sys.stderr, flush=True)
    c = Checks()
    c.add("rows_differ", rows, 0)
    c.add("steps_differ", steps, 0)
    c.add("map_off_ties", outside, 0)
    c.add("twt_rel_gap", twt_gap, LIMIT_METRIC_REL_GAP)
    c.add("makespan_rel_gap", mk_gap, LIMIT_METRIC_REL_GAP)
    return c
