"""Runtime layer: checkpoint roundtrip, pool, fault, elastic, campaign."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import checkpoint as CKPT
from repro.runtime.campaign import CampaignScheduler, CampaignStage
from repro.runtime.elastic import reshard_plan
from repro.runtime.fault import (HeartbeatTracker, StragglerMitigator,
                                 StragglerPolicy)
from repro.runtime.pool import ResourcePool


def _tree(key):
    return {
        "a": jax.random.normal(key, (8, 16)),
        "nested": {"b": jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
                   "c": jnp.float32(3.5)},
    }


def test_checkpoint_roundtrip(tmp_path):
    t = _tree(jax.random.PRNGKey(0))
    CKPT.save(t, tmp_path, 7)
    assert CKPT.latest_step(tmp_path) == 7
    r = CKPT.restore(t, tmp_path, 7)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_latest(tmp_path):
    t = _tree(jax.random.PRNGKey(1))
    th = CKPT.save_async(t, tmp_path, 1)
    th.join()
    CKPT.save(t, tmp_path, 5)
    assert CKPT.latest_step(tmp_path) == 5


def test_checkpoint_atomicity(tmp_path):
    """A directory without manifest.json is never considered restorable."""
    d = tmp_path / "step_9"
    d.mkdir(parents=True)
    (d / "a.bin").write_bytes(b"garbage")
    assert CKPT.latest_step(tmp_path) is None


def test_pool_claim_release_revoke():
    pool = ResourcePool()
    a1 = pool.add_allocation(4)
    pool.add_allocation(4)
    assert pool.available() == 8
    c = pool.claim(6)  # spans both allocations
    assert c is not None and pool.available() == 2
    revoked = []
    pool.on_revoke.append(lambda cl: revoked.append(cl.id))
    pool.remove_allocation(a1.id)
    assert revoked == [c.id]
    # the revoked spanning claim hands its slices back to the surviving
    # allocation — full capacity, not a leak (see test_pool_properties)
    assert pool.available() == 4
    assert pool.check_invariants() == []
    assert pool.claim(100) is None


def test_heartbeat_failure_detection():
    hb = HeartbeatTracker(timeout_s=10.0)
    hb.register(1, 0.0)
    hb.register(2, 0.0)
    hb.beat(1, 8.0)
    failed = hb.sweep(12.0)
    assert failed == [2]
    assert hb.healthy_count() == 1


def test_straggler_mitigation():
    sm = StragglerMitigator(StragglerPolicy(quantile=0.5, factor=2.0,
                                            min_samples=3))
    for i in range(5):
        sm.start(i, 0.0)
        sm.finish(i, 10.0)
    sm.start(99, 0.0)
    assert sm.stragglers(15.0) == []     # deadline = 10*2 = 20
    assert sm.stragglers(25.0) == [99]


def test_reshard_plan_reports_moves():
    from repro.parallel.sharding import ShardingRules

    class FakeMesh:
        def __init__(self, shape_map):
            self.shape = shape_map
            self.axis_names = tuple(shape_map)
    r16 = ShardingRules(FakeMesh({"data": 16, "model": 16}))
    r8 = ShardingRules(FakeMesh({"data": 8, "model": 16}))
    params = {"mlp": {"w_gate": jnp.zeros((4096, 16384))}}
    plan = reshard_plan(params, r16, r8)
    assert len(plan) == 1
    assert plan[0].bytes_total == 4096 * 16384 * 4


def test_campaign_overlaps_waits():
    """ASA campaign scheduling hides queue waits behind running stages."""
    from repro.sched.centers import UPPMAX
    from repro.sched.queue_sim import QueueSim
    from repro.sched.strategies import ASAEstimator

    est = ASAEstimator(seed=3)
    stages = [CampaignStage(f"s{i}", 160, 3000.0) for i in range(4)]
    # warm-up campaign (state persists, §4.3)
    sched0 = CampaignScheduler(QueueSim(UPPMAX, seed=11), est)
    sched0.sim.run_until(3600)
    sched0.run(stages)
    # measured campaign
    sim = QueueSim(UPPMAX, seed=12)
    sim.run_until(3600)
    rep = CampaignScheduler(sim, est).run(stages)
    waits = [o.real_wait_s for o in rep.outcomes]
    pwts = [o.perceived_wait_s for o in rep.outcomes[1:]]
    # later-stage perceived waits must be far below the raw queue waits
    assert sum(pwts) < 0.5 * sum(waits[1:])
    assert rep.makespan_s > 0


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_compile_cache_path(monkeypatch, env):
    """Unset: the cache goes to one fixed directory inside the checkout.
    Set: JAX keeps the variable's directory and nothing is set in code."""
    from repro.runtime import compile_cache

    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env is None:
        repo = compile_cache.REPO_CACHE_DIR.parent
        assert path == after == str(repo / ".jax_cache")
        assert (repo / ".gitignore").read_text().splitlines().count(
            ".jax_cache/") == 1
    else:
        assert path == env
        assert after == before
