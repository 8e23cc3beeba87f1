"""The program's own names in the profiler's trace.

- The jitted programs name their device work with ``jax.named_scope``:
  the sweep's phases (``xsim.events``, ``xsim.schedule`` with
  ``xsim.reserve`` inside it, ``xsim.hooks``) whatever computes the
  reservation, and the decision step's ``asa.update`` and ``asa.read``.
  The names are op metadata only: the compiled program is the same.
- With ``ServeConfig.obs_spans`` on, the serve loop writes each phase
  into the ``jax.profiler`` trace as an ``asa.serve.<phase>`` annotation
  on its own thread; with it off it creates no annotation and reads no
  clock.
"""

from __future__ import annotations

import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.serve import asa as serve_asa
from repro.serve.loop import ASAServer, ServeConfig
from repro.xsim import events, policies
from repro.xsim.grid import XSimConfig, make_grid

SWEEP_SCOPES = ("xsim.events", "xsim.schedule", "xsim.reserve",
                "xsim.hooks")
BATCH_PHASES = ("batch_form", "pad", "device_step", "scatter_read",
                "future_resolve")
_LOC = re.compile(r'loc\("([^"]*)"')


def _op_names(lowered) -> list[str]:
    """The name paths in the lowered program's location metadata, which
    become each compiled operation's ``op_name``."""
    return _LOC.findall(lowered.as_text(debug_info=True))


def _parts(op_name: str) -> list[str]:
    return op_name.split("/")


@pytest.mark.parametrize("freed_mode", ["ref", "interpret"])
def test_sweep_names_its_phases(freed_mode):
    cfg = XSimConfig(n_warm=4, n_backlog=4, n_arrivals=4, max_stages=9,
                     t0=3600.0)
    grid = make_grid(cfg, center_names=("hpc2n",), workflows=("montage",),
                     n_seeds=1)
    fleet = policies.init_fleet(int(grid.geo_idx.max()) + 1)
    ests = policies.scenario_estimators(fleet, jnp.asarray(grid.geo_idx), 1)
    states = jax.eval_shape(grid.build, ests)
    names = _op_names(events.sweep.lower(
        states, n_steps=cfg.n_steps, chunk_steps=cfg.chunk_steps,
        freed_mode=freed_mode, pred_mode=cfg.pred_mode, naive=False))
    for scope in SWEEP_SCOPES:
        assert any(scope in _parts(n) for n in names), scope
    reserve = [_parts(n) for n in names if "xsim.reserve" in _parts(n)]
    assert reserve and all(
        "xsim.schedule" in p[:p.index("xsim.reserve")] for p in reserve)
    # the reservation's own work (a sort on the reference path, the
    # kernel's operations under interpret) carries the name
    assert len(reserve) > 5


def _tiny_batch(n_slots=64, b=8):
    table = serve_asa.init_table(n_slots)
    q = serve_asa.QueryBatch(slot=jnp.arange(b, dtype=jnp.int32),
                             observed_wait=jnp.full((b,), 300.0),
                             has_obs=jnp.arange(b) % 2 == 0)
    return table, q, jnp.ones((b,), bool)


def test_serve_step_names_update_and_read():
    table, q, mask = _tiny_batch()
    names = _op_names(jax.jit(serve_asa.serve_step).lower(table, q, mask))
    assert any("asa.update" in _parts(n) for n in names)
    assert any("asa.read" in _parts(n) for n in names)


def _annotations(logdir) -> list[tuple[str, str, int, int]]:
    """(line, name, start, end) of every host annotation in the trace."""
    path = next(logdir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(("asa.serve.", "test.")):
                        out.append((f"{plane.name}#{i}", e.name,
                                    e.start_ns, e.start_ns + e.duration_ns))
    return out


def _serve_traced(tmp_path):
    """A started server with spans on answering 24 requests inside a
    profiler trace and a main-thread annotation; returns the trace's
    annotations and the number of batches dispatched in it."""
    server = ASAServer(ServeConfig(n_slots=16, batch_size=4,
                                   obs_spans=True))
    server.start()
    try:
        for f in [server.submit(t) for t in range(8)]:   # admissions
            f.result(timeout=60)
        before = int(server.obs.c_batches.value)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("test.main"):
                for k in range(3):
                    futs = [server.submit(t, 100.0 * (k + 1) if t % 2
                                          else None) for t in range(8)]
                    for f in futs:
                        f.result(timeout=60)
                    time.sleep(0.02)         # the loop idles meanwhile
        finally:
            jax.profiler.stop_trace()
        # the loop is between batches once every future has resolved:
        # any batch after the trace stopped is not in it
        batches = int(server.obs.c_batches.value) - before
    finally:
        server.stop()
    return _annotations(tmp_path), batches


def test_serve_phases_in_profiler_trace(tmp_path):
    anns, batches = _serve_traced(tmp_path)
    main_lines = {ln for ln, name, _a, _b in anns if name == "test.main"}
    serve = [a for a in anns if a[1].startswith("asa.serve.")]
    lines = {ln for ln, *_ in serve}
    # all on one thread, the loop's, not the main thread
    assert len(lines) == 1 and not lines & main_lines
    count = {p: sum(1 for a in serve if a[1] == "asa.serve." + p)
             for p in BATCH_PHASES}
    assert batches > 0
    # a batch whose future_resolve closed just after the trace stopped
    # is in it but for that phase
    assert all(count[p] in (batches, batches + 1) for p in BATCH_PHASES)
    assert count["future_resolve"] == batches
    busy = [(a, b) for _ln, name, a, b in serve
            if name[len("asa.serve."):] in BATCH_PHASES]
    idle = [(a, b) for _ln, name, a, b in serve
            if name == "asa.serve.idle"]
    assert idle
    assert not any(a < d and c < b for a, b in idle for c, d in busy)


def test_spans_off_creates_no_annotation_and_reads_no_clock(monkeypatch):
    """The off path: no ``TraceAnnotation`` is built and no code of the
    program reads ``time.perf_counter``, on the loop thread or in
    ``submit``; the same probe counts both with spans on."""
    made, reads = [], []
    real_clock = time.perf_counter

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    def clock():
        if "/repro/" in sys._getframe(1).f_code.co_filename:
            reads.append(threading.current_thread().name)
        return real_clock()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(time, "perf_counter", clock)
    counts = {}
    for spans in (False, True):
        server = ASAServer(ServeConfig(n_slots=16, batch_size=4,
                                       obs_spans=spans))
        made.clear()
        reads.clear()        # the recorder's epoch, read at construction
        server.start()
        try:
            for k in range(3):
                futs = [server.submit(t, 50.0 * (k + 1)) for t in range(6)]
                for f in futs:
                    f.result(timeout=60)
            time.sleep(0.01)
        finally:
            server.stop()
        counts[spans] = (len(made), len(reads))
    assert counts[False] == (0, 0)
    assert counts[True][0] > 0 and counts[True][1] > 0
