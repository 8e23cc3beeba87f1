"""ASA-as-a-service: decision semantics, batching invariants, durability.

The restart tests pin the ISSUE's acceptance bar literally: a server
restored from its checkpoint answers **bitwise-identical** decisions to
the uninterrupted server — posteriors AND per-slot PRNG keys — both
immediately after restore and after identical continued traffic.
"""

import shutil
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import asa as core_asa
from repro.core.bins import make_bins
from repro.parallel import fleet as pfleet
from repro.runtime import checkpoint as CKPT
from repro.serve import asa as serve_asa
from repro.serve.loop import ASAServer, ServeConfig, TableFullError

BINS = make_bins(53)


def _cfg(tmp_path=None, **kw):
    kw.setdefault("n_slots", 8)
    kw.setdefault("batch_size", 4)
    if tmp_path is not None:
        kw.setdefault("checkpoint_dir", str(tmp_path / "ckpt"))
    return ServeConfig(**kw)


def _decide(server, tenants):
    futs = [server.submit(t) for t in tenants]
    while any(not f.done() for f in futs):
        server.step_once(wait_s=0)
    return [f.result(timeout=10) for f in futs]


# ------------------------------------------------------------- decisions
def test_fresh_tenant_answers_prior_map():
    """A new tenant's lead time is the uniform prior's MAP = bins[0]."""
    server = ASAServer(_cfg())
    (d,) = _decide(server, [17])
    assert d.lead_s == pytest.approx(float(BINS[0]))
    # uniform posterior: entropy = ln m
    assert d.entropy == pytest.approx(float(np.log(53)), rel=1e-5)


def test_observations_move_the_posterior():
    """Repeated observations of a long wait pull the MAP to its bin —
    the tuned §4.5 update, same as the xsim engine applies."""
    server = ASAServer(_cfg())
    for _ in range(6):
        fut = server.submit(7, observed_wait=900.0)
        server.step_once(wait_s=0)
        d = fut.result(timeout=10)
    # bins are geometric; the MAP must land on the bin nearest 900s
    nearest = float(BINS[np.argmin(np.abs(np.asarray(BINS) - 900.0))])
    assert d.lead_s == pytest.approx(nearest)
    # update-then-decide: the answering posterior saw its own update,
    # so entropy has dropped strictly below the uniform ln m
    assert d.entropy < np.log(53) - 1e-3


def test_update_then_decide_within_one_batch():
    """A query that both observes and decides answers from the
    post-scatter table (its own fresh posterior), not the stale one."""
    table = serve_asa.init_table(4)
    q = serve_asa.QueryBatch(
        slot=jnp.array([2], jnp.int32),
        observed_wait=jnp.array([900.0], jnp.float32),
        has_obs=jnp.array([True]))
    qp, mask = pfleet.pad_batch(q, 4)
    new_table, dec = serve_asa.serve_step(table, qp, mask)
    # the decision row reflects the updated slot exactly
    row = jax.tree.map(lambda x: x[2], new_table)
    feats = core_asa.posterior_features(row, jnp.asarray(BINS, jnp.float32))
    assert float(dec.lead_s[0]) == float(feats[0])
    assert float(dec.entropy[0]) == float(feats[2])
    assert float(dec.entropy[0]) < np.log(53) - 1e-6


def test_pad_rows_never_touch_the_table():
    """pad_batch pads with copies of row 0 — including its observation.
    The mask must keep those copies out of the scatter."""
    table = serve_asa.init_table(4)
    q = serve_asa.QueryBatch(
        slot=jnp.array([1], jnp.int32),
        observed_wait=jnp.array([500.0], jnp.float32),
        has_obs=jnp.array([True]))
    qp, mask = pfleet.pad_batch(q, 8)
    assert int(mask.sum()) == 1
    once, _ = serve_asa.serve_step(table, qp, mask)
    # 8 padded copies of the same observing query must equal ONE update
    alone, _ = serve_asa.serve_step(
        table, jax.tree.map(lambda x: x[:1], qp), jnp.ones(1, bool))
    np.testing.assert_array_equal(np.asarray(once.log_p[1]),
                                  np.asarray(alone.log_p[1]))
    # untouched slots are bitwise the originals
    for s in (0, 2, 3):
        np.testing.assert_array_equal(np.asarray(once.log_p[s]),
                                      np.asarray(table.log_p[s]))
        np.testing.assert_array_equal(np.asarray(once.key[s]),
                                      np.asarray(table.key[s]))


# -------------------------------------------------------------- batching
def _mixed_stream(seed, n=16):
    """Requests of a few tenants, tenant 3 observing up to 6 times,
    interleaved with decide-only requests of the same tenants."""
    rng = np.random.default_rng(seed)
    tenants = rng.choice([1, 3, 3, 3, 5], size=n)
    waits = rng.uniform(20.0, 9000.0, n)
    obs = rng.random(n) < 0.6
    obs[np.flatnonzero((tenants == 3) & obs)[6:]] = False
    return [(int(t), float(w) if o else None)
            for t, w, o in zip(tenants, waits, obs)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_same_slot_observations_fold_as_served_one_by_one(seed):
    """Serial equivalence: one batch holding several observations of a
    slot answers what a server taking one request per batch answers,
    and leaves the same table."""
    reqs = _mixed_stream(seed)
    assert sum(t == 3 and w is not None for t, w in reqs) >= 2
    batched = ASAServer(_cfg(batch_size=16))
    futs = [batched.submit(t, w) for t, w in reqs]
    assert batched.step_once(wait_s=0) == len(reqs)      # one batch
    single = ASAServer(_cfg(batch_size=1))
    ones = []
    for t, w in reqs:
        ones.append(single.submit(t, w))
        single.step_once(wait_s=0)
    for a, b in zip(jax.tree.leaves(batched._table),
                    jax.tree.leaves(single._table)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for fa, fb in zip(futs, ones):
        a, b = fa.result(timeout=10), fb.result(timeout=10)
        assert a.lead_s == b.lead_s
        assert a.expected_s == pytest.approx(b.expected_s, rel=1e-6)
        assert a.entropy == pytest.approx(b.entropy, rel=1e-6)
    assert batched.obs.c_deferrals.value == 0


def test_zipf_traffic_matches_the_reference():
    """Seeded Zipf-skewed tenants through ``submit`` on 64 slots, in
    batches that repeat the hottest tenants' observations: every answer
    against Algorithm 1 replayed per tenant, held to serial equivalence
    (the Zipf cell's check)."""
    from chipbench.drivers import decide_zipf
    from chipbench.generator import Requests

    n_ten, n = 48, 400
    rng = np.random.default_rng(16)
    p = 1.0 / np.arange(1, n_ten + 1) ** 0.99
    tenant = rng.permutation(n_ten)[rng.choice(n_ten, n, p=p / p.sum())]
    waits = np.clip(rng.lognormal(8.0, 0.7, n), 1.0, 1e5).astype(np.float32)
    reqs = Requests(np.zeros(n), tenant,
                    np.where(rng.random(n) < 0.5, waits, np.nan))
    server = ASAServer(_cfg(n_slots=64, batch_size=32, seed=5))
    _decide(server, list(range(n_ten)))       # tenant i holds slot i
    futs = [server.submit(int(t), None if np.isnan(w) else float(w))
            for t, w in zip(reqs.tenant, reqs.wait_s)]
    while any(not f.done() for f in futs):
        server.step_once(wait_s=0)
    assert server.obs.c_folded.value > 0
    out = {"reqs": reqs, "results": [f.result() for f in futs],
           "failed": 0, "ctx": {"slot_seed": 5}}
    cfg = {"serve": {"n_slots": 64, "tenants": n_ten}}
    checks = decide_zipf.check(out, cfg, np.arange(n_ten))
    assert checks.ok, checks.as_dict()


def test_table_full_fails_the_future_not_the_loop():
    server = ASAServer(_cfg(n_slots=2, batch_size=4))
    f1 = server.submit(1)
    f2 = server.submit(2)
    f3 = server.submit(3)
    server.step_once(wait_s=0)
    assert f1.result(timeout=10) and f2.result(timeout=10)
    with pytest.raises(TableFullError):
        f3.result(timeout=10)
    # the loop survived: eviction frees a slot and serving continues
    server.evict(1)
    f4 = server.submit(4)
    server.step_once(wait_s=0)
    assert f4.result(timeout=10).tenant == 4


def test_evicted_slot_resets_on_reuse():
    server = ASAServer(_cfg(n_slots=1, batch_size=2))
    for _ in range(4):
        fut = server.submit(11, observed_wait=900.0)
        server.step_once(wait_s=0)
    assert fut.result(timeout=10).lead_s > float(BINS[0])
    server.evict(11)
    f = server.submit(12)
    server.step_once(wait_s=0)
    # the reused slot is back at the uniform prior
    assert f.result(timeout=10).lead_s == pytest.approx(float(BINS[0]))


def test_threaded_loop_serves_many_tenants():
    server = ASAServer(_cfg(n_slots=64, batch_size=16))
    server.start()
    try:
        futs = [server.submit(t, observed_wait=50.0 * (1 + t % 5))
                for t in range(48)]
        decs = [f.result(timeout=60) for f in futs]
    finally:
        server.stop()
    assert {d.tenant for d in decs} == set(range(48))
    assert server.stats["tenants"] == 48
    assert server.stats["deferred"] == 0


# ------------------------------------------------------------ durability
def _traffic(server, rounds=3):
    rng = np.random.default_rng(5)
    for r in range(rounds):
        for t in range(5):
            fut = server.submit(t, float(rng.uniform(20, 2000)))
            server.step_once(wait_s=0)
            fut.result(timeout=10)


def test_restart_is_bitwise_identical(tmp_path):
    cfg = _cfg(tmp_path)
    server = ASAServer(cfg)
    _traffic(server)
    server.save(step=3)
    restored = ASAServer.restore(cfg, step=3)

    # identical decisions right after restore
    da = _decide(server, range(5))
    db = _decide(restored, range(5))
    for a, b in zip(da, db):
        assert (a.lead_s, a.expected_s, a.entropy) == \
               (b.lead_s, b.expected_s, b.entropy)

    # identical continued traffic stays bitwise identical (PRNG keys
    # were restored exactly, so the tuned update's draws line up)
    for t in range(5):
        fa = server.submit(t, observed_wait=333.0)
        fb = restored.submit(t, observed_wait=333.0)
        server.step_once(wait_s=0)
        restored.step_once(wait_s=0)
        a, b = fa.result(timeout=10), fb.result(timeout=10)
        assert (a.lead_s, a.expected_s, a.entropy) == \
               (b.lead_s, b.expected_s, b.entropy)
    np.testing.assert_array_equal(np.asarray(server._table.log_p),
                                  np.asarray(restored._table.log_p))
    np.testing.assert_array_equal(np.asarray(server._table.key),
                                  np.asarray(restored._table.key))


def test_restore_latest_and_tenant_map(tmp_path):
    cfg = _cfg(tmp_path)
    server = ASAServer(cfg)
    _decide(server, [42, 7])
    server.evict(7)
    server.save(step=1)
    server.save(step=4)
    restored = ASAServer.restore(cfg)     # picks latest_step = 4
    assert restored._batches == 4
    assert restored._slot_of == server._slot_of
    assert set(restored._free) == set(server._free)
    # a freed slot of a restored table resets on reuse (unknown history)
    (d,) = _decide(restored, [99])
    assert d.lead_s == pytest.approx(float(BINS[0]))


def test_checkpoint_cadence_runs_async_saves(tmp_path):
    cfg = _cfg(tmp_path, checkpoint_every=2)
    server = ASAServer(cfg)
    _traffic(server, rounds=2)            # 10 batches -> 5 cadence saves
    server.stop()                         # collects the last handle
    assert CKPT.latest_step(cfg.checkpoint_dir) == 10


# ------------------------------------------------- checkpoint bug fixes
def test_save_async_failure_raises_at_join(tmp_path):
    """The daemon thread must not swallow exceptions: a failed
    background save re-raises from result()/join()."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file, not a directory")
    h = CKPT.save_async({"x": jnp.zeros(3)}, blocker / "sub", 1)
    with pytest.raises((NotADirectoryError, FileExistsError, OSError)):
        h.result(timeout=30)
    assert h.done()


def test_save_async_success_reports_path(tmp_path):
    h = CKPT.save_async({"x": jnp.arange(4)}, tmp_path, 2)
    path = h.result(timeout=30)
    assert path == tmp_path / "step_2"
    assert CKPT.latest_step(tmp_path) == 2


def test_server_save_async_failure_surfaces_on_next_save(tmp_path):
    cfg = _cfg(tmp_path)
    server = ASAServer(cfg)
    _decide(server, [1])
    h = server.save_async(step=1)
    h.result(timeout=30)
    # break the checkpoint dir: the NEXT save_async collects the failed
    # handle's result() and raises in the caller (the serve loop),
    # never silently
    shutil.rmtree(cfg.checkpoint_dir)
    Path(cfg.checkpoint_dir).write_text("now a file")
    server.save_async(step=2)
    with pytest.raises((NotADirectoryError, FileExistsError, OSError)):
        server.save_async(step=3)


def test_reused_tmp_dir_drops_stale_leaves(tmp_path):
    """A crashed save's leftover _tmp_step_* files must not leak into a
    later checkpoint of a *smaller* tree at the same step."""
    big = {"a": jnp.zeros(4), "b": jnp.ones(4)}
    small = {"a": jnp.zeros(4)}
    # simulate the crash: a tmp dir with the big tree's leaves, no
    # manifest (the rename never happened)
    tmp = tmp_path / "_tmp_step_5"
    tmp.mkdir()
    (tmp / "a.bin").write_bytes(b"stale")
    (tmp / "b.bin").write_bytes(b"stale")
    CKPT.save(small, tmp_path, 5)
    published = tmp_path / "step_5"
    names = {p.name for p in published.iterdir()}
    assert "b.bin" not in names, "stale leaf leaked into the checkpoint"
    r = CKPT.restore(small, tmp_path, 5)
    np.testing.assert_array_equal(np.asarray(r["a"]), np.zeros(4))
