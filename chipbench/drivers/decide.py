"""The live decision service: ``ASAServer.submit`` under an open loop.

Set-up starts one server and admits every tenant with one decide-only
request each, in the order of the tenant list (so tenant i holds slot
i); that also compiles the decision step. The server pads each batch
to its one compiled width with eager array operations whose programs
depend on how many requests the batch holds, so set-up also runs that
padding once for every count from 1 to the batch width: nothing
compiles in the window. The window then submits each
request at its due time, from this thread; a request's latency runs from
its due time to the moment its future resolves. After the window closes
every future is awaited (a minute at most) and a sample of tenants,
drawn from the seed, is replayed through the reference, request by
request.
"""

from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass

import numpy as np

from chipbench.common import Checks, CompileWatch, percentile, rng, sub_seed
from chipbench.generator import requests, tenant_ids

# limits of the comparison, from the readings in PERF.md
LIMIT_EXPECTED_REL_GAP = 1e-3
LIMIT_ENTROPY_ABS_GAP = 3e-3
DRAIN_S = 60.0
WARM_THREADS = 12


def server_config(cfg: dict, seed: int, spans: bool):
    from repro.serve.loop import ServeConfig

    s = cfg["serve"]
    return ServeConfig(n_slots=s["n_slots"], batch_size=s["batch_size"],
                       seed=sub_seed(seed, 6), obs_spans=spans)


def run(cell, cfg, traffic, seed, seconds, trace, devices, setup_t0,
        tracer):
    from repro.serve.loop import ASAServer

    ids = tenant_ids(cfg, seed)
    reqs = requests(traffic, cfg, seed, seconds)
    scfg = server_config(cfg, seed, spans=bool(trace))
    srv = ASAServer(scfg)
    srv.start()
    try:
        admit = [srv.submit(int(t)) for t in ids]
        for f in admit:
            f.result(timeout=600)
        _warm_padding(scfg.batch_size)
        gc.collect()
        gc.freeze()     # set-up's objects are never scanned in the window
        setup_s = time.perf_counter() - setup_t0
        out = _window(srv, ids, reqs, seconds, tracer, trace)
    finally:
        srv.stop()   # joins the loop: every done-callback has run
    _latencies(out)
    out["setup_s"] = setup_s
    out["ctx"]["serve_obs"] = srv.obs
    out["ctx"]["slot_seed"] = scfg.seed

    def run_check(peak_read):
        peak_read()
        return check(out, cfg, traffic, seed)

    out["check"] = run_check
    return out


def _warm_padding(width: int) -> None:
    """Run the server's batch padding at every live count it can meet,
    from a few threads. These thousands of small programs compile faster
    than the persistent cache loads them, so the cache is off meanwhile."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.parallel import fleet
    from repro.serve.asa import QueryBatch

    def pad(b):
        q = QueryBatch(slot=jnp.asarray(np.zeros(b, np.int32)),
                       observed_wait=jnp.asarray(np.zeros(b, np.float32)),
                       has_obs=jnp.asarray(np.zeros(b, bool)))
        jax.block_until_ready(fleet.pad_batch(q, width))

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with ThreadPoolExecutor(WARM_THREADS) as pool:
            list(pool.map(pad, range(1, width + 1)))
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _window(srv, ids, reqs, seconds, tracer, trace):
    n = reqs.due_s.shape[0]
    done_t = np.full(n, np.nan)
    sub_t = np.full(n, np.nan)
    futs = [None] * n

    def on_done(i):
        def cb(_f):
            done_t[i] = time.perf_counter()
        return cb

    snap0 = srv.obs.registry.snapshot()
    traced = tracer.start() if trace else False
    watch = CompileWatch().__enter__()
    t_open = time.perf_counter()
    due = t_open + reqs.due_s
    tenants = ids[reqs.tenant]
    waits = reqs.wait_s
    i = 0
    with tracer.annotate("chipbench.generator"):
        while i < n:
            now = time.perf_counter()
            if due[i] > now:
                time.sleep(min(due[i] - now, 0.05))
                continue
            with tracer.annotate("chipbench.submit"):
                while i < n and due[i] <= time.perf_counter():
                    w = waits[i]
                    sub_t[i] = time.perf_counter()
                    f = srv.submit(int(tenants[i]),
                                   None if np.isnan(w) else float(w))
                    f.add_done_callback(on_done(i))
                    futs[i] = f
                    i += 1
    t_close = t_open + seconds
    while time.perf_counter() < t_close:
        time.sleep(min(t_close - time.perf_counter(), 0.05))
    window_s = time.perf_counter() - t_open
    watch.__exit__()
    if traced:
        tracer.stop()
    results = []
    failed = 0
    deadline = time.perf_counter() + DRAIN_S
    for f in futs:
        try:
            results.append(f.result(timeout=max(deadline - time.perf_counter(),
                                                0.0)))
        except Exception:  # a shed, failed or late request: no answer
            results.append(None)
            failed += 1
    snap1 = srv.obs.registry.snapshot()
    ctx = {"gen_lag_ms": (sub_t - due) * 1e3, "n_requests": n,
           "counters": (snap0, snap1)}
    return {"ctx": ctx, "attempted": n, "failed": failed, "results": results,
            "reqs": reqs, "ids": ids, "times": (due, done_t, t_open,
                                                window_s)}


def _latencies(out) -> None:
    """Latency from each request's due time; one that never resolved
    counts as infinitely late."""
    due, done_t, t_open, window_s = out.pop("times")
    lat = np.where(np.isnan(done_t), np.inf, done_t - due) * 1e3
    resolved_in = int(np.sum(done_t <= t_open + window_s))
    out["e2e"] = {"decide_p50_ms": percentile(lat, 50),
                  "decide_p95_ms": percentile(lat, 95),
                  "decide_per_s": resolved_in / window_s}


def check(out, cfg, traffic, seed) -> Checks:
    """A sample of tenants, every request of each, against the reference."""
    import jax

    from chipbench.reference.alg1 import BINS, best_branch

    t0 = time.perf_counter()
    reqs, results = out["reqs"], out["results"]
    n_ten = cfg["serve"]["tenants"]
    k = min(traffic.get("check_tenants", n_ten), n_ten)
    sample = np.sort(rng(seed, 7).choice(n_ten, size=k, replace=False))
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        keys = jax.random.split(jax.random.PRNGKey(out["ctx"]["slot_seed"]),
                                cfg["serve"]["n_slots"])[sample]
        draw = jax.jit(lambda key: (jax.random.split(key)[0],
                                    jax.random.gumbel(
                                        jax.random.split(key)[1],
                                        (BINS.shape[0],))))
    keys = np.asarray(keys)
    lead_bad = checked = ties = followed = 0
    exp_gap = ent_gap = 0.0
    by_tenant = {int(t): [] for t in sample}
    for i, t in enumerate(reqs.tenant):
        lst = by_tenant.get(int(t))
        if lst is not None:
            lst.append(i)

    def score(r):
        return (r.lead_bad, max(r.exp_gap / LIMIT_EXPECTED_REL_GAP,
                                r.ent_gap / LIMIT_ENTROPY_ABS_GAP))

    for j, t in enumerate(sample):
        idx = by_tenant[int(t)]
        gumbels, key = [], keys[j]
        for i in idx:                   # the key chain: one split per
            if not np.isnan(reqs.wait_s[i]):   # observation, whatever
                with jax.default_device(cpu):  # action it draws
                    key, g = draw(key)
                gumbels.append(np.asarray(g))
        r, picks = best_branch(
            lambda picks: _replay(gumbels, idx, reqs, results, picks), score)
        lead_bad += r.lead_bad
        exp_gap, ent_gap = max(exp_gap, r.exp_gap), max(ent_gap, r.ent_gap)
        checked += r.checked
        ties += len(r.ties)
        followed += sum(p != 0 for p in picks)
    print(f"reference: {checked} decisions of {k} tenants in "
          f"{time.perf_counter() - t0:.1f}s; {ties} choices near a tie, "
          f"{followed} of them followed to the other side",
          file=sys.stderr, flush=True)
    c = Checks()
    c.add("lead_off_ties", lead_bad, 0)
    c.add("failed_requests", out["failed"], 0)
    c.add("expected_rel_gap", exp_gap, LIMIT_EXPECTED_REL_GAP)
    c.add("entropy_abs_gap", ent_gap, LIMIT_ENTROPY_ABS_GAP)
    return c


@dataclass
class Replay:
    lead_bad: int
    exp_gap: float
    ent_gap: float
    checked: int
    ties: list


def _replay(gumbels, idx, reqs, results, picks) -> Replay:
    """One tenant's requests through the reference posterior, its ties
    resolved by ``picks``."""
    from chipbench.reference.alg1 import BINS, Alg1

    est = Alg1(None, picks)
    states = [_state(est)]          # posterior after each observation
    n_obs_before = []
    for i in idx:
        w = reqs.wait_s[i]
        if not np.isnan(w):
            est.learn(float(w), gumbels[len(states) - 1])
            states.append(_state(est))
        n_obs_before.append(len(states) - 1)
    r = Replay(0, 0.0, 0.0, 0, est.ties)
    for pos, i in enumerate(idx):
        d = results[i]
        if d is None:
            continue
        kk = n_obs_before[pos]
        cands = [states[kk]]
        if np.isnan(reqs.wait_s[i]) and kk + 1 < len(states):
            cands.append(states[kk + 1])   # its tenant's next update
        #                                    may share its batch
        ok_lead = any(np.float32(d.lead_s) in BINS[c[0]] for c in cands)
        r.lead_bad += int(not ok_lead)
        r.exp_gap = max(r.exp_gap, min(abs(d.expected_s - c[1]) / c[1]
                                       for c in cands))
        r.ent_gap = max(r.ent_gap, min(abs(d.entropy - c[2]) for c in cands))
        r.checked += 1
    return r


def _state(est):
    e, h = est.expected_and_entropy()
    return (np.asarray(est.map_choices()), e, h)
