#!/usr/bin/env python3
"""The control: the reference put in the program's place, in bfloat16.

The configurations state float32 (tables and times, posteriors). The
control computes the same answers one precision lower and is judged by
the comparison that judges the program; it has to come out as not
correct. For a sweep cell it simulates each scenario of one call with
every time rounded to bfloat16; for the decide cell it answers each
request from bfloat16 posteriors.

    python3 chipbench/control.py --workload uppmax.sweep --seeds 1 2 3

It prints, per seed, each compared number beside its limit, and a last
line of JSON with every reading. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def bf16(v):
    """Round float32 values to bfloat16 and back."""
    import ml_dtypes

    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def sweep_control(cfg, traffic, seed, devices):
    from chipbench.drivers import sweep as dsw
    from chipbench.generator import sweep_call
    from chipbench.reference import xsim as ref

    call = sweep_call(traffic, seed, 0)
    scen = ref.make_scenarios(cfg, call.workflow, traffic["seeds_per_call"],
                              call.grid_seed, device=devices[0])
    outs = [ref.simulate(s, cfg["xsim"]["step_budget"], rnd=bf16)
            for s in scen]
    host = {"start": np.stack([o.start for o in outs]),
            "end": np.stack([o.end for o in outs]),
            "status": np.stack([o.status for o in outs]),
            "steps": np.array([o.steps for o in outs]),
            "pred_wait": None,
            "twt": np.array([o.twt for o in outs], np.float32),
            "makespan": np.array([o.makespan for o in outs], np.float32)}
    return dsw.compare(cfg, traffic, call, host, seed, devices)


class _Answer:
    def __init__(self, lead, expected, entropy):
        self.lead_s, self.expected_s, self.entropy = lead, expected, entropy


def decide_control(cfg, traffic, seed, seconds):
    import jax
    import ml_dtypes

    from chipbench.common import sub_seed
    from chipbench.drivers import decide
    from chipbench.generator import requests
    from chipbench.reference.alg1 import BINS, Alg1

    reqs = requests(traffic, cfg, seed, seconds)
    slot_seed = sub_seed(seed, 6)
    n_slots = cfg["serve"]["n_slots"]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(slot_seed),
                                           n_slots))
        draw = jax.jit(lambda key: (jax.random.split(key)[0],
                                    jax.random.gumbel(
                                        jax.random.split(key)[1],
                                        (BINS.shape[0],))))
    results = [None] * reqs.tenant.shape[0]
    k = min(traffic.get("check_tenants", n_slots), cfg["serve"]["tenants"])
    from chipbench.common import rng

    sample = set(rng(seed, 7).choice(cfg["serve"]["tenants"], size=k,
                                     replace=False).tolist())
    ests = {}
    for i, t in enumerate(reqs.tenant):
        t = int(t)
        if t not in sample:
            continue
        est = ests.setdefault(t, Alg1(keys[t]))
        w = reqs.wait_s[i]
        if not np.isnan(w):
            with jax.default_device(cpu):
                key, g = draw(est.key)
            est.key = key
            est.learn(float(w), np.asarray(g))
        lp = est.log_p().astype(ml_dtypes.bfloat16)
        e, h = est.expected_and_entropy(ml_dtypes.bfloat16)
        results[i] = _Answer(float(BINS[int(np.argmax(lp))]), e, h)
    out = {"reqs": reqs, "results": results, "failed": 0,
           "ctx": {"slot_seed": slot_seed}}
    return decide.check(out, cfg, traffic, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import run

    cell, cfg, traffic, _, _ = run.load_cell(ROOT, args.workload)
    devices = jax.devices()[:cell["chips"]]
    readings = {}
    for seed in args.seeds:
        if traffic["kind"] == "sweep":
            c = sweep_control(cfg, traffic, seed, devices)
        else:
            c = decide_control(cfg, traffic, seed, args.seconds)
        c.print_stderr()
        readings[seed] = {"correct": c.ok, **c.as_dict()}
    print(json.dumps({"workload": args.workload,
                      "device": devices[0].device_kind,
                      "control": "reference in bfloat16",
                      "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
