"""The one traffic generator. A mix is a data file under ``traffic/``;
its ``kind`` says which of the two shapes of work it describes:

* ``sweep``: back-to-back what-if calls. Call k runs one workflow
  (cycling through ``workflows``) over the center's scales and the
  listed policies, ``seeds_per_call`` background seeds each, on a grid
  seed drawn from the run's seed and k.
* ``decide``: an open loop of decision requests. ``rate_per_s`` times
  the window's length requests, with exponential gaps rescaled to span
  the window exactly (the same count in every run), each from a tenant
  drawn uniformly, and a share ``obs_share`` carrying an observed stage
  wait drawn from the configuration's log-normal wait model.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench.common import rng, sub_seed


@dataclass(frozen=True)
class SweepCall:
    index: int
    workflow: str
    grid_seed: int


def sweep_call(traffic: dict, seed: int, k: int) -> SweepCall:
    wfs = traffic["workflows"]
    return SweepCall(k, wfs[k % len(wfs)], sub_seed(seed, 1, k))


@dataclass(frozen=True)
class Requests:
    due_s: np.ndarray       # (N,) seconds after the window opens
    tenant: np.ndarray      # (N,) index into the tenant list
    wait_s: np.ndarray      # (N,) observed wait; NaN where none


def tenant_ids(cfg: dict, seed: int) -> np.ndarray:
    """The tenants' ids: distinct, drawn from the seed, int32-sized."""
    n = cfg["serve"]["tenants"]
    return rng(seed, 2).choice(2**31 - 1, size=n, replace=False)


def requests(traffic: dict, cfg: dict, seed: int, seconds: float) -> Requests:
    r = rng(seed, 3)
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    gaps = r.exponential(1.0, n)
    due = np.cumsum(gaps) - gaps[0]
    due = due * (seconds / due[-1]) if n > 1 else np.zeros(1)
    tenant = r.integers(0, cfg["serve"]["tenants"], n)
    w = cfg["serve"]["wait_model"]
    waits = np.clip(r.lognormal(w["mu"], w["sigma"], n), w["min_s"],
                    w["max_s"]).astype(np.float32)
    has = r.random(n) < traffic["obs_share"]
    return Requests(due, tenant, np.where(has, waits, np.nan))
