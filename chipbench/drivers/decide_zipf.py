"""The decision service under Zipf-skewed tenants whose hot set moves.

The open loop of ``drivers/decide.py`` with one change: each request's
tenant. Due times, waits and which requests observe are drawn as
``generator.requests`` draws them. The tenant is the request's Zipf
rank, drawn over the configuration's tenants with exponent
``tenant_skew.s``, mapped through a permutation of the tenants that is
drawn again every ``tenant_skew.rehot_s`` seconds of due time (an
epoch), so a few tenants send most requests and which ones changes.
Set-up, window, latencies and per-layer context are ``decide.py``'s.
The check replays through the reference every request of the
seed-drawn ``check_tenants`` (as ``decide.py`` draws them) and of the
``check_hot`` hottest tenants of every epoch, and holds each answer to
serial equivalence: it must be the posterior after exactly the
observations of its tenant that precede it, its own included. (The
uniform cell's check also accepts a decide-only answer that has seen
its tenant's next observation.)
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench.common import Checks, rng
from chipbench.drivers.decide import (LIMIT_ENTROPY_ABS_GAP,
                                      LIMIT_EXPECTED_REL_GAP, Replay,
                                      _latencies, _state, _warm_padding,
                                      _window, server_config)
from chipbench.generator import Requests, requests, tenant_ids


def zipf_requests(traffic: dict, cfg: dict, seed: int, seconds: float):
    """The requests, and each epoch's tenants from hottest to coldest."""
    base = requests(traffic, cfg, seed, seconds)
    skew = cfg["tenant_skew"]
    n_ten = cfg["serve"]["tenants"]
    p = 1.0 / np.arange(1, n_ten + 1, dtype=np.float64) ** skew["s"]
    rank = rng(seed, 16).choice(n_ten, size=base.due_s.shape[0],
                                p=p / p.sum())
    epoch = (base.due_s // skew["rehot_s"]).astype(np.int64)
    order = [rng(seed, 17, e).permutation(n_ten)
             for e in range(int(epoch.max()) + 1)]
    tenant = np.empty_like(rank)
    for e, perm in enumerate(order):
        at = epoch == e
        tenant[at] = perm[rank[at]]
    return Requests(base.due_s, tenant, base.wait_s), order


def check_sample(traffic: dict, cfg: dict, seed: int, order) -> np.ndarray:
    n_ten = cfg["serve"]["tenants"]
    k = min(traffic["check_tenants"], n_ten)
    drawn = rng(seed, 7).choice(n_ten, size=k, replace=False)
    hot = [perm[:traffic["check_hot"]] for perm in order]
    return np.unique(np.concatenate([drawn, *hot]))


def run(cell, cfg, traffic, seed, seconds, trace, devices, setup_t0,
        tracer):
    from repro.serve.loop import ASAServer

    ids = tenant_ids(cfg, seed)
    reqs, order = zipf_requests(traffic, cfg, seed, seconds)
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
    sample = check_sample(traffic, cfg, seed, order)

    def run_check(peak_read):
        peak_read()
        return check(out, cfg, sample)

    out["check"] = run_check
    return out


def check(out, cfg, sample) -> Checks:
    """Every request of each sampled tenant against the reference."""
    import jax

    from chipbench.reference.alg1 import BINS, best_branch

    t0 = time.perf_counter()
    reqs, results = out["reqs"], out["results"]
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
        for i in idx:                   # one split per observation
            if not np.isnan(reqs.wait_s[i]):
                with jax.default_device(cpu):
                    key, g = draw(key)
                gumbels.append(np.asarray(g))
        r, picks = best_branch(
            lambda picks: _replay_serial(gumbels, idx, reqs, results, picks),
            score)
        lead_bad += r.lead_bad
        exp_gap, ent_gap = max(exp_gap, r.exp_gap), max(ent_gap, r.ent_gap)
        checked += r.checked
        ties += len(r.ties)
        followed += sum(p != 0 for p in picks)
    print(f"reference: {checked} decisions of {len(sample)} tenants in "
          f"{time.perf_counter() - t0:.1f}s; {ties} choices near a tie, "
          f"{followed} of them followed to the other side",
          file=sys.stderr, flush=True)
    c = Checks()
    c.add("lead_off_ties", lead_bad, 0)
    c.add("failed_requests", out["failed"], 0)
    c.add("expected_rel_gap", exp_gap, LIMIT_EXPECTED_REL_GAP)
    c.add("entropy_abs_gap", ent_gap, LIMIT_ENTROPY_ABS_GAP)
    return c


def _replay_serial(gumbels, idx, reqs, results, picks) -> Replay:
    """One tenant's requests through the reference posterior, its ties
    resolved by ``picks``: each answer against the one posterior serial
    equivalence allows."""
    from chipbench.reference.alg1 import BINS, Alg1

    est = Alg1(None, picks)
    cur = _state(est)
    r = Replay(0, 0.0, 0.0, 0, est.ties)
    n_obs = 0
    for i in idx:
        w = reqs.wait_s[i]
        if not np.isnan(w):
            est.learn(float(w), gumbels[n_obs])
            n_obs += 1
            cur = _state(est)
        d = results[i]
        if d is None:
            continue
        r.lead_bad += int(np.float32(d.lead_s) not in BINS[cur[0]])
        r.exp_gap = max(r.exp_gap, abs(d.expected_s - cur[1]) / cur[1])
        r.ent_gap = max(r.ent_gap, abs(d.entropy - cur[2]))
        r.checked += 1
    return r
