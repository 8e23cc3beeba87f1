"""Server observability: registry, lifecycle spans, scrape, merged trace.

Pins the load-bearing contracts of the serve-side observability layer:

- the metrics registry is stdlib-only (imports without jax), counters
  are monotone, histograms bucket like the paper's m = 53 ladder, and
  the Prometheus text exposition is format-0.0.4 shaped;
- **span conservation**: every request entering ``submit()`` produces
  exactly one ``enqueue`` event and exactly one ``request`` resolve
  span — TableFullError resolutions and eviction races included — and
  ``requests_total == resolved_total + failed_total`` once drained;
- decisions are **bit-identical** with spans on, spans off, and on the
  uninstrumented pre-observability path (the registry is counters-only
  bookkeeping; it must never touch device numerics);
- ``stats`` keeps its PR-7 keys while no longer losing evicted tenants'
  request counts (folded into the registry at evict time);
- the merged Chrome trace interleaves serve pid rows with device event
  rings without id collisions and passes ``validate_chrome``;
- the scrape endpoint serves /metrics (Prometheus), /metrics.json and
  /stats from the stdlib HTTP server, with monotone counters between
  scrapes;
- the telemetry schema knows ``serve_metrics``, treats unknown kinds as
  warn-level (never a hard failure), and bench_gate keys open/closed
  serve legs apart, gates batching health, and fails when the
  serve_metrics leg is missing.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request
from collections import Counter as TallyCounter

import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import registry as reg
from repro.obs import telemetry
from repro.obs.serve_obs import (PHASES, SERVE_PID, SERVE_REQUEST_PID,
                                 ServeObs, serve_registry)
from repro.serve.loop import ASAServer, ServeConfig


def _cfg(**kw):
    kw.setdefault("n_slots", 8)
    kw.setdefault("batch_size", 4)
    return ServeConfig(**kw)


def _drain_all(server, futs, max_steps=64):
    steps = 0
    while any(not f.done() for f in futs):
        server.step_once(wait_s=0)
        steps += 1
        assert steps < max_steps, "requests not draining"
    return futs


# --------------------------------------------------------- registry unit


def test_geometric_buckets_shape_and_errors():
    b = reg.geometric_buckets(1e-4, 100.0)
    assert len(b) == reg.M_BUCKETS_DEFAULT == 53
    assert b[0] == pytest.approx(1e-4) and b[-1] == pytest.approx(100.0)
    assert list(b) == sorted(b)
    # constant ratio: geometric ladder like core.bins.make_bins
    r = np.diff(np.log(np.asarray(b)))
    np.testing.assert_allclose(r, r[0], rtol=1e-9)
    with pytest.raises(ValueError):
        reg.geometric_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        reg.geometric_buckets(2.0, 1.0)
    with pytest.raises(ValueError):
        reg.geometric_buckets(1.0, 2.0, n=1)


def test_counter_monotone_and_gauge():
    r = reg.Registry()
    c = r.counter("x_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3


def test_histogram_bucketing_and_overflow():
    h = reg.Histogram("lat", (1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):  # le is inclusive: 1.0 -> bucket 0
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == [1.0, 2.0, 4.0]
    assert snap["counts"] == [2, 0, 1, 1]  # last = +Inf overflow
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(104.5)
    h.observe_many([0.1, 9.0])
    assert h.snapshot()["counts"] == [3, 0, 1, 2]
    with pytest.raises(ValueError):
        reg.Histogram("bad", (3.0, 1.0))


def test_registry_get_or_create_and_kind_clash():
    r = reg.Registry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(TypeError):
        r.gauge("a")
    assert r.get("a").kind == "counter"
    assert r.get("nope") is None


def test_prometheus_text_format():
    r = reg.Registry()
    r.counter("asa_x_total", "things").inc(3)
    r.gauge("asa_depth").set(2.5)
    r.histogram("asa_lat", (1.0, 2.0), "waits").observe_many([0.5, 5.0])
    text = r.prometheus_text()
    lines = text.splitlines()
    assert "# HELP asa_x_total things" in lines
    assert "# TYPE asa_x_total counter" in lines
    assert "asa_x_total 3" in lines
    assert "# TYPE asa_depth gauge" in lines
    assert "asa_depth 2.5" in lines
    # cumulative buckets + the implicit +Inf, then sum/count
    assert 'asa_lat_bucket{le="1"} 1' in lines
    assert 'asa_lat_bucket{le="2"} 1' in lines
    assert 'asa_lat_bucket{le="+Inf"} 2' in lines
    assert "asa_lat_sum 5.5" in lines
    assert "asa_lat_count 2" in lines
    assert text.endswith("\n")


def test_registry_snapshot_and_json_line():
    r = serve_registry()
    r.counter("asa_serve_requests_total").inc(7)
    snap = r.snapshot()
    assert snap["asa_serve_requests_total"] == 7
    assert snap["asa_serve_request_latency_seconds"]["count"] == 0
    line = json.loads(r.json_line(ts=123.0))
    assert line["ts"] == 123.0
    assert line["asa_serve_requests_total"] == 7


def test_registry_stays_importable_without_jax():
    # the gate-side tooling reads snapshots from a bare checkout: the
    # registry module must never drag jax in (same contract as
    # repro.obs.telemetry)
    import importlib.util
    import subprocess
    import sys
    spec = importlib.util.find_spec("repro.obs.registry")
    src_root = spec.origin.rsplit("/repro/", 1)[0]
    code = ("import sys; sys.modules['jax'] = None\n"
            f"sys.path.insert(0, {src_root!r})\n"
            "import repro.obs.registry as r\n"
            "reg = r.Registry(); reg.counter('c').inc()\n"
            "assert 'c 1' in reg.prometheus_text().splitlines()\n")
    subprocess.run([sys.executable, "-c", code], check=True)


# ------------------------------------------------------ span conservation


def _tally(obs: ServeObs) -> TallyCounter:
    return TallyCounter(ev[1] for ev in obs.events)


def _request_rids(obs: ServeObs, name: str) -> list[int]:
    return [ev[6] for ev in obs.events
            if ev[1] == name and ev[2] == SERVE_REQUEST_PID]


def test_span_conservation_happy_path():
    server = ASAServer(_cfg(obs_spans=True, batch_size=8))
    futs = [server.submit(t % 3, observed_wait=50.0 * (1 + t % 4))
            for t in range(12)]
    _drain_all(server, futs)
    o = server.obs
    enq = _request_rids(o, "enqueue")
    res = _request_rids(o, "request")
    assert sorted(enq) == sorted(res)          # one resolve per enqueue
    assert len(set(enq)) == len(enq) == 12     # unique rids, all 12
    s = server.stats
    assert s["requests"] == 12
    assert int(o.c_resolved.value) + s["failed"] == 12
    assert o.g_inflight.value == 0


def test_span_conservation_table_full():
    server = ASAServer(_cfg(n_slots=1, batch_size=4, obs_spans=True))
    f_ok = server.submit(1)
    f_full = server.submit(2)                  # no slot left
    server.step_once(wait_s=0)
    assert f_ok.result(timeout=10).tenant == 1
    assert f_full.exception(timeout=10) is not None
    o = server.obs
    assert sorted(_request_rids(o, "enqueue")) == \
        sorted(_request_rids(o, "request"))
    # the failed request's span carries the error marker
    errors = [ev[7] for ev in o.events if ev[1] == "request"]
    assert errors.count("table_full") == 1
    assert _tally(o)["table_full"] == 1        # admission-lane instant
    assert server.stats["failed"] == 1
    assert server.stats["table_full"] == 1
    assert o.g_inflight.value == 0


def test_span_conservation_eviction_race():
    """A tenant evicted between submit and dispatch is re-admitted at
    batch-form time; the request still resolves exactly once."""
    server = ASAServer(_cfg(obs_spans=True))
    f0 = server.submit(5, observed_wait=700.0)
    server.step_once(wait_s=0)
    f0.result(timeout=10)
    f1 = server.submit(5)                      # in queue...
    server.evict(5)                            # ...tenant vanishes
    server.step_once(wait_s=0)
    d = f1.result(timeout=10)
    assert d.tenant == 5
    o = server.obs
    assert sorted(_request_rids(o, "enqueue")) == \
        sorted(_request_rids(o, "request"))
    assert _tally(o)["evict"] == 1
    assert server.stats["evicted_tenants"] == 1
    assert o.g_inflight.value == 0


def test_fold_counters_conserve():
    """One batch with 3, 2 and 1 observations of three tenants folds 3
    observations in 3 rounds; a decide-only batch runs its one round;
    nothing is deferred, and every request still resolves exactly
    once."""
    server = ASAServer(_cfg(obs_spans=True, batch_size=16))
    reqs = [(3, 100.0), (5, 80.0), (3, None), (3, 200.0), (1, 50.0),
            (5, 90.0), (3, 300.0), (1, None)]
    futs = [server.submit(t, w) for t, w in reqs]
    assert server.step_once(wait_s=0) == len(reqs)
    futs += [server.submit(t) for t in (1, 3)]
    _drain_all(server, futs)
    o = server.obs
    assert int(o.c_batches.value) == 2
    assert int(o.c_folded.value) == 3
    assert int(o.c_fold_rounds.value) == 3 + 1
    assert int(o.c_deferrals.value) == 0 and "defer" not in _tally(o)
    assert sorted(_request_rids(o, "enqueue")) == \
        sorted(_request_rids(o, "request"))
    assert o.rates()["defer_rate"] == 0.0


# ------------------------------------------------- bit-identity + stats


def _profiler_names(logdir) -> set[str]:
    from jax.profiler import ProfileData

    path = next(logdir.rglob("*.xplane.pb"))
    return {e.name for p in ProfileData.from_file(str(path)).planes
            for ln in p.lines for e in ln.events}


def test_decisions_bit_identical_spans_on_off(tmp_path):
    """The acceptance bar: the registry-off default path answers bitwise
    what the fully-instrumented server answers — observability is host
    bookkeeping only, it never touches device numerics. Each server runs
    under the profiler: spans off write no ``asa.serve.*``
    annotation into its trace, spans on write every batch phase."""
    import jax

    traffic = [(t % 4, 60.0 * (1 + t % 5)) for t in range(16)]
    answers = []
    for spans in (False, True):
        server = ASAServer(_cfg(obs_spans=spans))
        logdir = tmp_path / f"spans_{spans}"
        with jax.profiler.trace(str(logdir)):
            futs = [server.submit(t, observed_wait=w) for t, w in traffic]
            _drain_all(server, futs)
        answers.append([(d.lead_s, d.expected_s, d.entropy)
                        for d in (f.result(timeout=10) for f in futs)])
        written = {n for n in _profiler_names(logdir)
                   if n.startswith("asa.serve.")}
        if spans:
            assert {f"asa.serve.{p}" for p in PHASES[:5]} <= written
        else:
            assert len(server.obs.events) == 0   # no spans recorded
            assert not written
    assert answers[0] == answers[1]


def test_stats_keeps_evicted_tenant_request_counts():
    """The PR-7 stats() bug: evicting a tenant silently dropped its
    request counts.  Now the lifetime total folds into the registry at
    evict time and stats() reports it."""
    server = ASAServer(_cfg())
    for _ in range(3):
        f = server.submit(7, observed_wait=100.0)
        server.step_once(wait_s=0)
        f.result(timeout=10)
    f = server.submit(8)
    server.step_once(wait_s=0)
    f.result(timeout=10)
    server.evict(7)
    s = server.stats
    # backward-compatible PR-7 keys, same meanings
    for k in ("batches", "decisions", "tenants", "n_slots", "deferred"):
        assert k in s
    assert s["decisions"] == 4 and s["tenants"] == 1
    # the evicted tenant's lifetime is not lost
    assert s["evicted_tenants"] == 1
    assert s["evicted_requests"] == 3
    assert s["requests"] == 4
    # a second eviction accumulates
    server.evict(8)
    assert server.stats["evicted_requests"] == 4


def test_spans_off_takes_no_timestamps():
    o = ServeObs(spans=False)
    assert o.now() == 0.0
    o.enqueue(0, 1, 0.0)
    o.span("batch_form", 0.0, 0.0)
    o.instant("admit", 0.0)
    assert len(o.events) == 0 and o.events_dropped == 0


def test_span_buffer_bounded_drops_oldest():
    o = ServeObs(spans=True, span_capacity=4)
    for i in range(7):
        o.enqueue(i, 0, float(i))
    assert len(o.events) == 4
    assert o.events_dropped == 3
    assert [ev[6] for ev in o.events] == [3, 4, 5, 6]   # oldest dropped


# ------------------------------------------------------- chrome export


def _small_served_obs():
    server = ASAServer(_cfg(obs_spans=True))
    futs = [server.submit(t % 3, observed_wait=80.0 * (1 + t % 3))
            for t in range(9)]
    _drain_all(server, futs)
    return server.obs


def test_chrome_events_shape():
    o = _small_served_obs()
    evs = o.chrome_events()
    names = {e["name"] for e in evs}
    assert {"process_name", "serve_obs_meta", "enqueue",
            "request"} <= names
    by_pid = TallyCounter(e["pid"] for e in evs)
    assert by_pid[SERVE_PID] > 0 and by_pid[SERVE_REQUEST_PID] > 0
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
            assert "ts" in e
        elif e["ph"] == "i":
            assert e["s"] == "t"
    # loop-phase spans present under their documented names
    loop_names = {e["name"] for e in evs if e["pid"] == SERVE_PID}
    assert set(PHASES[:5]) <= loop_names
    # request-lane args carry rid + tenant
    req = next(e for e in evs if e["name"] == "request")
    assert {"rid", "tenant"} <= set(req["args"])


def test_merged_trace_serve_only(tmp_path):
    o = _small_served_obs()
    meta = obs_export.write_merged_trace(str(tmp_path / "m.json"),
                                         serve=o)
    obj = json.loads((tmp_path / "m.json").read_text())
    assert obs_export.validate_chrome(obj) == []
    assert obj["otherData"]["serve_pid"] == SERVE_PID
    assert obj["otherData"]["n_scenarios"] == 0
    assert meta["serve_events_kept"] == len(o.events)
    assert meta["serve_events_dropped"] == 0
    with pytest.raises(ValueError, match="needs"):
        obs_export.merged_chrome_trace()


@pytest.fixture(scope="module")
def traced_sweep():
    """A tiny traced xsim sweep: the device event rings the merged
    trace interleaves with the serve rows."""
    from repro.xsim import policies
    from repro.xsim.grid import XSimConfig, make_grid, run_grid
    from repro.xsim.state import ASA
    cfg = XSimConfig(n_warm=8, n_backlog=6, n_arrivals=8, max_stages=9,
                     t0=1800.0).with_trace()
    grid = make_grid(cfg, center_names=("hpc2n",), workflows=("blast",),
                     policy_ids=(ASA,), n_seeds=1, shrink=1 / 64.0)
    fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    final, _ = run_grid(grid, fleet, pred_seed=3)
    return final, grid.labels


def test_merged_trace_roundtrip_no_pid_collisions(tmp_path, traced_sweep):
    final, labels = traced_sweep
    o = _small_served_obs()
    path = tmp_path / "merged.json"
    meta = obs_export.write_merged_trace(str(path), final, labels, o)
    obj = json.loads(path.read_text())
    assert obs_export.validate_chrome(obj) == []
    pids = {e["pid"] for e in obj["traceEvents"]}
    scen = {p for p in pids if p < SERVE_PID}
    assert scen == set(range(obj["otherData"]["n_scenarios"]))
    assert {SERVE_PID, SERVE_REQUEST_PID} <= pids
    assert obj["otherData"]["serve_request_pid"] == SERVE_REQUEST_PID
    # both sources fully present: device events + serve events + metas
    n_serve = sum(1 for e in obj["traceEvents"] if e["pid"] >= SERVE_PID)
    assert n_serve == len(o.chrome_events())
    assert meta["events_total"] == len(obj["traceEvents"])
    # the reserved-pid guard trips instead of colliding
    fake = {"traceEvents": [], "displayTimeUnit": "ms",
            "otherData": {"format": "repro.obs.chrome_trace",
                          "version": 1, "n_scenarios": SERVE_PID + 1}}
    import unittest.mock as mock
    with mock.patch.object(obs_export, "chrome_trace",
                           return_value=fake):
        with pytest.raises(ValueError, match="reserved serve pid"):
            obs_export.merged_chrome_trace(final, labels, o)


# ------------------------------------------------------- scrape endpoint


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read()


def test_scrape_endpoint_smoke():
    server = ASAServer(_cfg())
    port = server.serve_metrics_http(port=0)
    try:
        f = server.submit(1, observed_wait=100.0)
        server.step_once(wait_s=0)
        f.result(timeout=10)
        status, ctype, body = _get(port, "/metrics")
        assert status == 200 and "version=0.0.4" in ctype
        text = body.decode()
        assert "# TYPE asa_serve_requests_total counter" in text
        first = _scrape_value(text, "asa_serve_requests_total")
        # more traffic, scrape again: counters are monotone between
        # scrapes of one process (the registry contract CI smokes)
        f = server.submit(2)
        server.step_once(wait_s=0)
        f.result(timeout=10)
        _, _, body2 = _get(port, "/metrics")
        second = _scrape_value(body2.decode(), "asa_serve_requests_total")
        assert second == first + 1
        status, ctype, body = _get(port, "/metrics.json")
        assert status == 200 and ctype == "application/json"
        snap = json.loads(body)
        assert snap["asa_serve_requests_total"] == 2
        status, _, body = _get(port, "/stats")
        assert json.loads(body) == server.stats
        with pytest.raises(urllib.error.HTTPError):
            _get(port, "/nope")
        with pytest.raises(RuntimeError, match="already running"):
            server.serve_metrics_http(port=0)
    finally:
        server.stop_metrics_http()


def _scrape_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"{name} not exposed")


def test_metrics_port_config_starts_endpoint_with_loop():
    server = ASAServer(_cfg(metrics_port=0))
    server.start()
    try:
        port = server._http.server_address[1]
        status, _, _ = _get(port, "/metrics")
        assert status == 200
    finally:
        server.stop()
    assert server._http is None               # stop() tears it down


# ------------------------------------------------- checkpoint stall span


def test_checkpoint_stall_recorded(tmp_path):
    cfg = _cfg(checkpoint_dir=str(tmp_path / "ckpt"), obs_spans=True)
    server = ASAServer(cfg)
    f = server.submit(1)
    server.step_once(wait_s=0)
    f.result(timeout=10)
    server.save_async(step=1).result(timeout=30)
    server.save_async(step=2).result(timeout=30)  # collects handle 1
    o = server.obs
    assert int(o.c_checkpoints.value) == 2
    assert _tally(o)["checkpoint_stall"] == 1
    assert float(o.c_ckpt_stall_s.value) >= 0.0


# --------------------------------------------------- telemetry schema


def test_serve_metrics_kind_validates():
    rec = telemetry.record(
        "serve_metrics",
        run={"label": "t"},
        profile={"pad_fraction": 0.1, "defer_rate": 0.2,
                 "serve_obs_overhead_frac": 0.01},
        metrics={"asa_serve_requests_total": 5},
        trace=None)
    assert telemetry.validate(rec) == []
    leg = telemetry.serve_metrics_leg(rec)
    assert leg["pad_fraction"] == 0.1
    assert leg["asa_serve_requests_total"] == 5
    bad = {"telemetry_version": 1, "kind": "serve_metrics",
           "run": {}, "profile": {"pad_fraction": 0.1},
           "metrics": {}, "trace": None}
    errs = telemetry.validate(bad)
    assert any("defer_rate" in e for e in errs)
    with pytest.raises(ValueError, match="defer_rate"):
        telemetry.serve_metrics_leg(bad)


def test_unknown_kind_is_warn_level_not_failure():
    rec = {"telemetry_version": 1, "kind": "kind_from_the_future",
           "run": {}, "profile": {}, "metrics": {}, "trace": None}
    msgs = telemetry.validate(rec)
    assert len(msgs) == 1 and telemetry.is_warning(msgs[0])
    assert "kind" in msgs[0]
    assert telemetry.hard_errors(msgs) == []
    # record() accepts forward-compatible kinds (warn, not raise)...
    telemetry.record("kind_from_the_future", run={}, profile={},
                     metrics={}, trace=None)
    # ...but still hard-fails on a missing section, warnings aside
    broken = {"telemetry_version": 1, "kind": "kind_from_the_future",
              "run": {}, "metrics": {}, "trace": None}
    assert telemetry.hard_errors(telemetry.validate(broken)) != []


def test_serve_leg_flattens_mode_and_rates():
    rec = telemetry.record(
        "serve_latency",
        run={"label": "closed64", "mode": "closed", "n_shards": None},
        profile={"p50_ms": 3.0, "p99_ms": 30.0,
                 "decisions_per_sec": 1000.0, "pad_fraction": 0.8},
        metrics={"defer_rate": 0.1},           # older records: in metrics
        trace=None)
    leg = telemetry.serve_leg(rec)
    assert leg["mode"] == "closed"
    assert leg["pad_fraction"] == 0.8          # profile wins
    assert leg["defer_rate"] == 0.1            # metrics fallback
    # mode defaults open for pre-closed-loop records
    rec2 = telemetry.record(
        "serve_latency", run={"label": "smoke"},
        profile={"p50_ms": 1.0, "p99_ms": 2.0,
                 "decisions_per_sec": 5.0},
        metrics={}, trace=None)
    assert telemetry.serve_leg(rec2)["mode"] == "open"


# ------------------------------------------------------- bench_gate


def test_serve_leg_key_separates_modes():
    from benchmarks import bench_gate
    assert bench_gate.serve_leg_key({"mode": "open"}) == "serve"
    assert bench_gate.serve_leg_key({}) == "serve"
    assert bench_gate.serve_leg_key({"mode": "closed"}) == "serve-closed"
    assert bench_gate.serve_leg_key(
        {"mode": "closed", "n_shards": 8}) == "serve-closed-shards8"


def test_gate_serve_checks_latency_and_batching_health():
    from benchmarks import bench_gate
    baseline = {"legs": {
        "serve": {"decisions_per_sec": 1000.0, "pad_fraction_max": 0.5,
                  "defer_rate_max": 1.0},
        "serve-closed": {"p50_ms": 4.0, "p99_ms": 100.0,
                         "pad_fraction_max": 0.9},
    }}
    good = {
        "serve": {"decisions_per_sec": 1100.0, "pad_fraction": 0.3,
                  "defer_rate": 0.6},
        "serve-closed": {"p50_ms": 4.5, "p99_ms": 110.0,
                         "pad_fraction": 0.85,
                         "decisions_per_sec": 500.0},
    }
    rec, fails = bench_gate.gate_serve(good, baseline, tolerance=0.25)
    assert rec["ok"] and fails == []
    bad = {
        "serve": {"decisions_per_sec": 500.0, "pad_fraction": 0.7,
                  "defer_rate": 1.4},
        "serve-closed": {"p50_ms": 40.0, "p99_ms": 90.0,
                         "pad_fraction": 0.95},
    }
    rec, fails = bench_gate.gate_serve(bad, baseline, tolerance=0.25)
    assert not rec["ok"]
    named = " | ".join(fails)
    assert "decisions/sec" in named
    assert "pad_fraction" in named and "defer_rate" in named
    assert "p50" in named
    # a baseline-gated metric missing from the record must not pass
    rec, fails = bench_gate.gate_serve(
        {"serve": {"decisions_per_sec": 1100.0, "defer_rate": 0.1},
         "serve-closed": good["serve-closed"]},
        baseline, tolerance=0.25)
    assert any("no pad_fraction" in f for f in fails)


def test_missing_serve_metrics_leg_fails_the_gate(tmp_path):
    from benchmarks import bench_gate
    open_rec = telemetry.record(
        "serve_latency", run={"label": "smoke", "mode": "open"},
        profile={"p50_ms": 1.0, "p99_ms": 2.0,
                 "decisions_per_sec": 9000.0},
        metrics={}, trace=None)
    (tmp_path / "serve_latency_smoke.json").write_text(
        json.dumps(open_rec))
    legs, fails = bench_gate.collect_serve_metrics_legs(tmp_path)
    assert legs == {} and fails == []          # absence named in main()
    met = telemetry.record(
        "serve_metrics", run={"label": "smoke"},
        profile={"pad_fraction": 0.2, "defer_rate": 0.5,
                 "serve_obs_overhead_frac": 0.02},
        metrics={"asa_serve_requests_total": 10,
                 "asa_serve_deferrals_total": 5}, trace=None)
    (tmp_path / "serve_metrics_smoke.json").write_text(json.dumps(met))
    legs, fails = bench_gate.collect_serve_metrics_legs(tmp_path)
    assert fails == [] and "serve-metrics" in legs
    assert legs["serve-metrics"]["asa_serve_deferrals_total"] == 5
    # a malformed serve_metrics record is a NAMED failure
    (tmp_path / "serve_metrics_broken.json").write_text(json.dumps(
        {"telemetry_version": 1, "kind": "serve_metrics",
         "run": {"label": "oops"}, "profile": {}, "metrics": {},
         "trace": None}))
    _, fails = bench_gate.collect_serve_metrics_legs(tmp_path)
    assert any("oops" in f and "pad_fraction" in f for f in fails)
