"""Fault-tolerant serving: chaos schedules, crash containment and
recovery, checkpoint integrity, shedding, and pressure eviction.

Pins the ISSUE's robustness acceptance bars literally:

* a failing jitted step fails **that batch's** futures with a typed
  ``ServeStepError`` and the loop survives;
* a crashed loop restarts from the latest checkpoint and the restarted
  server's decisions are **bitwise** those of the uninterrupted run
  (nothing replayed);
* a corrupted latest checkpoint degrades to the previous verified step
  (``latest_step(verified=True)``), and plain ``restore`` of the
  corrupted step raises ``CheckpointCorruptError``;
* a full table with an idle tenant sheds the coldest lease through
  ``runtime.pool`` instead of raising ``TableFullError``;
* every submitted future resolves — with a Decision or a typed error —
  under any chaos interleaving (the hypothesis property at the end).
"""

import dataclasses
import random
import tempfile
import time
import urllib.error
import urllib.request
from http.client import RemoteDisconnected
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime import checkpoint as CKPT
from repro.serve import asa as serve_asa
from repro.serve import chaos as schaos
from repro.serve.loop import (ASAServer, QueueFullError, RequestExpired,
                              ServeConfig, ServeSupervisor, ServerCrashed,
                              ServerStopped, TableFullError)


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


def _probe(server, tenants):
    """Decide-only probes: pure table reads, safe for bitwise compares
    regardless of batch composition."""
    return [(d.lead_s, d.expected_s, d.entropy)
            for d in _decide(server, tenants)]


# ------------------------------------------------------------- schedules
def test_chaos_event_validation():
    with pytest.raises(ValueError, match="unknown chaos kind"):
        schaos.ChaosEvent(0, "meteor_strike")
    with pytest.raises(ValueError, match="batch must be >= 0"):
        schaos.ChaosEvent(-1, "step_exception")
    with pytest.raises(ValueError, match="magnitude > 0"):
        schaos.slow_step(3, 0.0)
    with pytest.raises(ValueError, match="magnitude >= 1"):
        schaos.queue_burst(3, 0)


def test_chaos_schedule_sorts_and_rejects_duplicates():
    s = schaos.ChaosSchedule((schaos.crash(5), schaos.step_exception(1),
                              schaos.checkpoint_error(1)))
    assert [e.batch for e in s.events] == [1, 1, 5]
    # within a batch, CHAOS_KINDS order is the total firing order
    assert [e.kind for e in s.events[:2]] == \
        ["step_exception", "checkpoint_write_error"]
    with pytest.raises(ValueError, match="duplicate chaos event"):
        schaos.ChaosSchedule((schaos.crash(2), schaos.crash(2)))


def test_mix_schedule_is_deterministic():
    a = schaos.mix_schedule(20, seed=7)
    b = schaos.mix_schedule(20, seed=7)
    assert a.events == b.events
    assert len(a) == 9  # 3 step + 1 slow + 2 ckpt + 1 crash + 2 burst


def test_injector_fires_at_or_after_and_once():
    inj = schaos.ChaosInjector(schaos.ChaosSchedule(
        (schaos.step_exception(3),)))
    inj.before_device_step(0)          # before the arm batch: nothing
    assert len(inj.pending) == 1
    with pytest.raises(schaos.InjectedStepFault):
        inj.before_device_step(7)      # at-or-after: fires late, once
    assert inj.pending == ()
    inj.before_device_step(7)          # never re-fires
    assert inj.counts()["step_exception"] == 1


# ----------------------------------------------------------- containment
def test_step_exception_fails_the_batch_not_the_loop():
    inj = schaos.ChaosInjector(schaos.ChaosSchedule(
        (schaos.step_exception(0),)))
    server = ASAServer(_cfg(), chaos=inj)
    futs = [server.submit(t) for t in (1, 2, 3)]
    server.step_once(wait_s=0)
    for f in futs:
        err = f.exception(timeout=10)
        assert isinstance(err, serve_asa.ServeStepError)
        assert err.batch == 0
        assert isinstance(err.__cause__, schaos.InjectedStepFault)
    # the failed dispatch neither commits the table nor counts a batch
    assert server.stats["batches"] == 0
    assert server.stats["step_errors"] == 1
    # the loop survives: the very next step serves normally
    (d,) = _decide(server, [9])
    assert d.lead_s > 0
    assert server.stats["batches"] == 1


def test_checkpoint_write_error_is_contained(tmp_path):
    inj = schaos.ChaosInjector(schaos.ChaosSchedule(
        (schaos.checkpoint_error(0),)))
    server = ASAServer(_cfg(tmp_path, checkpoint_every=1), chaos=inj)
    _decide(server, [1, 2])            # cadence fires, injection raises
    assert server.stats["batches"] >= 1          # serving continued
    reg = server.obs.registry.snapshot()
    assert reg["asa_serve_checkpoint_failures_total"] >= 1
    # later cadences save normally once the fault has fired
    _decide(server, [3, 4])
    server.stop()                      # collects the async handle
    assert CKPT.latest_step(server.cfg.checkpoint_dir) is not None


# -------------------------------------------------------- crash recovery
def test_crash_recovery_is_bitwise_with_uninterrupted_run(tmp_path):
    """The acceptance bar: a supervisor-restarted server answers the
    exact decisions of a server that never crashed, because restore
    replays nothing — both continue from the same checkpoint bytes."""
    cfg = _cfg(tmp_path)
    ref = ASAServer(cfg)               # the uninterrupted reference
    for t in range(6):
        fut = ref.submit(t, observed_wait=250.0 * (t + 1))
        ref.step_once(wait_s=0)
        fut.result(timeout=10)
    ref.save(step=3)

    # the crashing run: same checkpoint on disk, then a crash before
    # any further traffic lands — the supervisor restores from step 3
    inj = schaos.ChaosInjector(schaos.ChaosSchedule(
        (schaos.crash(0),)))
    sup = ServeSupervisor(cfg, chaos=inj)
    sup.start()
    try:
        fut = sup.submit(0)            # trips the batch-boundary crash
        deadline = time.monotonic() + 30
        while sup.restarts == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sup.restarts == 1
        # the pre-crash future resolved one way or the other (typed)
        err = fut.exception(timeout=30)
        assert err is None or isinstance(err, ServerCrashed)
        # post-restart traffic serves
        assert sup.submit(1).result(timeout=30).lead_s > 0
    finally:
        sup.stop()

    # bitwise: restore the supervisor's recovery checkpoint directly
    # and probe decide-only against the uninterrupted reference
    restored = ASAServer.restore(cfg, step=3, verified=True)
    assert _probe(restored, range(6)) == _probe(ref, range(6))
    np.testing.assert_array_equal(np.asarray(restored._table.log_p),
                                  np.asarray(ref._table.log_p))
    np.testing.assert_array_equal(np.asarray(restored._table.key),
                                  np.asarray(ref._table.key))


def test_crash_drains_pending_with_typed_error():
    inj = schaos.ChaosInjector(schaos.ChaosSchedule((schaos.crash(0),)))
    server = ASAServer(_cfg(), chaos=inj)
    futs = [server.submit(t) for t in range(5)]
    with pytest.raises(schaos.InjectedCrash):
        server.step_once(wait_s=0)     # manual stepping: crash escapes
    server._crash(schaos.InjectedCrash("boom"))  # what _run would do
    for f in futs:
        assert isinstance(f.exception(timeout=10), ServerCrashed)
    with pytest.raises(ServerCrashed):
        server.submit(99)              # ingress rejects after a crash
    with pytest.raises(ServerCrashed, match="cannot start"):
        server.start()
    assert server.stats["crashes"] == 1


def test_watchdog_gauges_track_loop_health():
    server = ASAServer(_cfg())
    server.start()
    try:
        server.submit(1).result(timeout=30)
        snap = server.obs.registry.snapshot()
        assert snap["asa_serve_loop_healthy"] == 1.0
        assert snap["asa_serve_last_batch_age_seconds"] >= 0.0
    finally:
        server.stop()
    assert server.obs.registry.snapshot()["asa_serve_loop_healthy"] == 0.0


# ------------------------------------------------------------- integrity
def test_corrupted_latest_falls_back_to_verified_step(tmp_path):
    cfg = _cfg(tmp_path)
    server = ASAServer(cfg)
    _decide(server, [1, 2, 3])
    server.save(step=1)
    _decide(server, [4, 5])
    server.save(step=2)
    ckpt_dir = tmp_path / "ckpt"
    assert CKPT.verify_step(ckpt_dir, 2) == []

    # flip one byte in a leaf of the latest step
    leaf = sorted((ckpt_dir / "step_2").glob("*.bin"))[0]
    raw = bytearray(leaf.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    leaf.write_bytes(bytes(raw))

    assert CKPT.verify_step(ckpt_dir, 2) != []
    assert CKPT.latest_step(ckpt_dir) == 2              # unverified view
    assert CKPT.latest_step(ckpt_dir, verified=True) == 1
    with pytest.raises(CKPT.CheckpointCorruptError):
        ASAServer.restore(cfg, step=2)
    # verified restore degrades to the previous good step — and its
    # decisions are the step-1 server's, bitwise
    restored = ASAServer.restore(cfg, verified=True)
    assert restored._batches == 1
    ref = ASAServer.restore(cfg, step=1)
    assert _probe(restored, [1, 2, 3]) == _probe(ref, [1, 2, 3])


# --------------------------------------------------- shedding & eviction
def test_full_table_sheds_coldest_lease_not_table_full():
    cfg = _cfg(n_slots=4, tenant_ttl_s=30.0)
    server = ASAServer(cfg)
    for t in range(4):                 # fill the table, oldest first
        _decide(server, [t])
    for t in range(1, 4):              # touch 1..3: tenant 0 is coldest
        _decide(server, [t])
    (d,) = _decide(server, [77])       # full table: sheds, not fails
    assert d.lead_s > 0
    assert 77 in server._slot_of and 0 not in server._slot_of
    assert server.stats["lease_evictions"] == 1
    assert server.stats["table_full"] == 0


def test_idle_lease_expires_and_frees_the_slot():
    cfg = _cfg(n_slots=2, tenant_ttl_s=0.05)
    server = ASAServer(cfg)
    _decide(server, [1])
    time.sleep(0.08)                   # tenant 1's lease lapses
    _decide(server, [2])               # sweep frees it on admit
    _decide(server, [3])
    assert 1 not in server._slot_of
    assert {2, 3} <= set(server._slot_of)


def test_default_config_still_raises_table_full():
    server = ASAServer(_cfg(n_slots=2))
    _decide(server, [1, 2])
    fut = server.submit(3)
    server.step_once(wait_s=0)
    assert isinstance(fut.exception(timeout=10), TableFullError)


def test_in_batch_tenants_are_never_shed():
    """Every tenant of the forming batch is protected: when all slot
    holders are in THIS batch, the overflow tenant fails table-full —
    pressure eviction never steals a protected slot mid-batch (slot
    reuse inside one scatter would break the unique-slot invariant)."""
    cfg = _cfg(n_slots=2, batch_size=4, tenant_ttl_s=30.0)
    server = ASAServer(cfg)
    futs = [server.submit(t) for t in (10, 11, 12)]  # 3 tenants, 2 slots
    server.step_once(wait_s=0)
    assert futs[0].result(timeout=10).lead_s > 0
    assert futs[1].result(timeout=10).lead_s > 0
    assert isinstance(futs[2].exception(timeout=10), TableFullError)
    assert server.stats["lease_evictions"] == 0
    assert set(server._slot_of) == {10, 11}   # nobody was stolen from
    # once the batch has left, 12 admits by shedding an idle lease
    (d,) = _decide(server, [12])
    assert d.lead_s > 0 and server.stats["lease_evictions"] == 1


def test_queue_full_sheds_with_typed_error():
    server = ASAServer(_cfg(max_queue=2))
    f1, f2 = server.submit(1), server.submit(2)
    f3 = server.submit(3)
    assert isinstance(f3.exception(timeout=1), QueueFullError)
    assert server.stats["shed"] == 1
    reg = server.obs.registry.snapshot()
    assert reg["asa_serve_shed_queue_full_total"] == 1
    while not (f1.done() and f2.done()):  # the accepted two still serve
        server.step_once(wait_s=0)
    assert f1.result(timeout=10).lead_s > 0
    assert f2.result(timeout=10).lead_s > 0


def test_deadline_shed_at_batch_form():
    server = ASAServer(_cfg())
    dead = server.submit(1, deadline_s=1e-6)
    live = server.submit(2, deadline_s=60.0)
    time.sleep(0.01)
    server.step_once(wait_s=0)
    assert isinstance(dead.exception(timeout=10), RequestExpired)
    assert live.result(timeout=10).lead_s > 0
    reg = server.obs.registry.snapshot()
    assert reg["asa_serve_shed_expired_total"] == 1
    assert reg["asa_serve_shed_total"] == 1


# ------------------------------------------------------------- lifecycle
def test_stop_drains_and_fails_queued_with_server_stopped():
    server = ASAServer(_cfg())
    futs = [server.submit(t) for t in range(4)]   # never stepped
    server.stop()
    for f in futs:
        assert isinstance(f.exception(timeout=10), ServerStopped)
    with pytest.raises(ServerStopped):
        server.submit(99)
    assert server.obs.registry.snapshot()[
        "asa_serve_stop_drained_total"] == 4


def test_repeated_stop_is_idempotent():
    server = ASAServer(_cfg())
    server.start()
    server.submit(1).result(timeout=30)
    server.stop()
    server.stop()                      # second stop: no-op, no raise
    server.stop_metrics_http()
    server.stop_metrics_http()


def test_scrape_racing_shutdown_answers_500(monkeypatch):
    server = ASAServer(_cfg())
    port = server.serve_metrics_http(port=0)
    url = f"http://127.0.0.1:{port}/stats"
    assert urllib.request.urlopen(url, timeout=5).status == 200
    # simulate the race: the stats view tears down mid-scrape
    monkeypatch.setattr(
        ASAServer, "stats",
        property(lambda self: (_ for _ in ()).throw(
            RuntimeError("teardown race"))))
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=5)
        assert exc.value.code == 500
    except RemoteDisconnected:  # pragma: no cover
        pytest.fail("handler died on the socket instead of answering 500")
    finally:
        monkeypatch.undo()
        server.stop_metrics_http()


# --------------------------------------------------------------- property
@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=10**9))
def test_every_future_resolves_under_chaos(seed):
    """Random submit/observe/evict interleavings against a seeded chaos
    schedule (step faults + a crash + a burst), served by a supervisor:
    every submitted future resolves — a Decision or a typed error — and
    the surviving checkpoint restores bitwise."""
    rng = random.Random(seed)
    with tempfile.TemporaryDirectory(prefix="chaos_prop_") as tmp:
        _chaos_property_body(seed, rng, Path(tmp))


def _chaos_property_body(seed, rng, tmp):
    cfg = ServeConfig(n_slots=6, batch_size=4,
                      checkpoint_dir=str(tmp / "ckpt"),
                      checkpoint_every=2, max_queue=64,
                      tenant_ttl_s=5.0)
    events = [schaos.step_exception(rng.randrange(1, 6)),
              schaos.crash(rng.randrange(1, 6))]
    if rng.random() < 0.5:
        burst_b = rng.randrange(1, 6)
        if all(e.batch != burst_b or e.kind != "queue_burst"
               for e in events):
            events.append(schaos.queue_burst(burst_b, 8))
    inj = schaos.ChaosInjector(schaos.ChaosSchedule(tuple(events)),
                               seed=seed)
    sup = ServeSupervisor(cfg, chaos=inj)
    futs = []
    sup.start()
    try:
        for _ in range(rng.randrange(10, 30)):
            op = rng.random()
            tenant = rng.randrange(10)
            if op < 0.5:
                futs.append(sup.submit(tenant))
            elif op < 0.8:
                futs.append(sup.submit(
                    tenant, observed_wait=rng.uniform(10.0, 4000.0)))
            else:
                try:
                    sup.server.evict(tenant)
                except (KeyError, ServerCrashed):
                    pass               # unknown tenant / mid-restart
            if rng.random() < 0.3:
                time.sleep(0.002)
        deadline = time.monotonic() + 120
        for f in futs + list(inj.burst_futures):
            remaining = deadline - time.monotonic()
            assert remaining > 0, "futures still pending at deadline"
            err = f.exception(timeout=remaining)
            assert err is None or isinstance(err, RuntimeError), \
                f"untyped error {err!r}"
    finally:
        sup.stop()
    step = CKPT.latest_step(cfg.checkpoint_dir, verified=True)
    if step is not None:
        # the probes write no checkpoints: two restored servers saving
        # the same step into one directory, in threads nobody joins,
        # would race each other and the temp dir's removal
        probe_cfg = dataclasses.replace(cfg, checkpoint_every=0)
        a = ASAServer.restore(probe_cfg, step=step, verified=True)
        b = ASAServer.restore(probe_cfg, step=step, verified=True)
        assert _probe(a, range(10)) == _probe(b, range(10))
        np.testing.assert_array_equal(np.asarray(a._table.log_p),
                                      np.asarray(b._table.log_p))
