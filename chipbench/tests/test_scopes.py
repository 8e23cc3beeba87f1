"""The scope reader, on a small trace recorded on this CPU.

The CPU backend runs its operations on host threads and writes no
device plane, so the test records the decision step's two programs, a
served batch and the harness's window on this CPU, then appends a
device plane laid out as the chip's (an ``XLA Ops`` line whose events
name compiled instructions, nested, and an ``XLA Modules`` line) to the
same file, and reads it back through ``chipbench/scopes.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes

NS = 1000   # ps per ns


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines, names) -> bytes:
    """An XPlane: ``lines`` is [(line name, t0_ns, [(meta id, start_ns,
    dur_ns)])], ``names`` {meta id: event name}."""
    body = _field(1, 99) + _field(2, name)
    for i, (lname, t0, evs) in enumerate(lines):
        ev = b"".join(_field(4, _field(1, m) + _field(2, (a - t0) * NS)
                             + _field(3, d * NS)) for m, a, d in evs)
        body += _field(3, _field(1, i) + _field(2, lname) + _field(3, t0)
                       + ev)
    for mid, mname in names.items():
        body += _field(4, _field(1, mid) + _field(2, _field(1, mid)
                                                 + _field(2, mname)))
    return _field(1, body)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The path of a CPU trace of the decision step's programs and a few
    served batches inside ``chipbench.window``."""
    from repro.serve import asa as serve_asa
    from repro.serve.loop import ASAServer, ServeConfig

    logdir = tmp_path_factory.mktemp("trace")
    table = serve_asa.init_table(64)
    q = serve_asa.QueryBatch(slot=jnp.arange(8, dtype=jnp.int32),
                             observed_wait=jnp.full((8,), 300.0),
                             has_obs=jnp.ones((8,), bool))
    server = ASAServer(ServeConfig(n_slots=16, batch_size=4,
                                   obs_spans=True))
    server.start()
    try:
        jax.profiler.start_trace(str(logdir))
        with jax.profiler.TraceAnnotation("chipbench.window"):
            jax.block_until_ready(serve_asa.serve_step(
                table, q, jnp.ones((8,), bool)))
            for f in [server.submit(t, 100.0) for t in range(6)]:
                f.result(timeout=60)
        jax.profiler.stop_trace()
    finally:
        server.stop()
    return next(logdir.rglob("*.xplane.pb"))


def _programs(path):
    mods = scopes.module_scopes(str(path))
    by_scope = {}
    for pid, names in mods.items():
        for ins, sc in names.items():
            by_scope.setdefault(sc, (pid, ins))
    return mods, by_scope


def test_module_scopes_name_the_decision_step(recorded):
    mods, by_scope = _programs(recorded)
    assert {"asa.update", "asa.read"} <= set(by_scope)
    # the two programs are separate modules (one per compiled shape): a
    # module holds one of the two scopes, never both
    scoped = [set(names.values()) & {"asa.update", "asa.read"}
              for names in mods.values()]
    assert all(len(sc) == 1 for sc in scoped if sc)


def test_innermost_scope():
    f = scopes.innermost_scope
    assert f("jit(sweep)/while/body/xsim.schedule/xsim.reserve/sort") \
        == "xsim.reserve"
    assert f("jit(f)/vmap(xsim.events)/add") == "xsim.events"
    assert f("jit(sweep)/while/body/add") is None
    assert f("") is None


def test_self_times_nest_and_sum_to_the_union():
    evs = [(0, 100, "while"), (10, 40, "a"), (20, 30, "b"), (50, 90, "c"),
           (200, 210, "d")]
    got = scopes.self_times(evs)
    assert got == {"while": 30, "a": 20, "b": 10, "c": 40, "d": 10}
    assert sum(got.values()) == 110


def _with_device_plane(recorded, tmp_path):
    """The recorded trace plus a chip-like device plane whose ops are
    instructions of the two decision programs: a ``while`` of the
    update program holding one update op and one unscoped op, then a
    read op, then an op that runs past the window's end."""
    from jax.profiler import ProfileData

    mods, by_scope = _programs(recorded)
    pd = ProfileData.from_file(str(recorded))
    win = next(e for p in pd.planes if p.name.startswith("/host")
               for ln in p.lines for e in ln.events
               if e.name == "chipbench.window")
    w0 = int(win.start_ns)
    w1 = w0 + int(win.duration_ns)
    up_pid, up_ins = by_scope["asa.update"]
    rd_pid, rd_ins = by_scope["asa.read"]
    none_ins = next(i for i, sc in mods[up_pid].items() if sc is None)
    names = {1: "while.1", 2: up_ins, 3: none_ins, 4: rd_ins,
             10: f"jit__update_body({up_pid})",
             11: f"jit__read_decisions({rd_pid})"}
    t = w0 + 1000
    ops = [(1, t, 600), (2, t + 100, 200), (3, t + 400, 100),
           (4, t + 800, 300), (4, w1 - 50, 200)]
    modules = [(10, t, 700), (11, t + 800, 300), (11, w1 - 60, 300)]
    plane = _plane("/device:TPU:0", [("XLA Modules", w0, modules),
                                     ("XLA Ops", w0, ops)], names)
    path = tmp_path / "with_device.xplane.pb"
    path.write_bytes(recorded.read_bytes() + plane)
    return path, (w0, w1)


def test_summary_attributes_self_time_to_scopes(recorded, tmp_path):
    path, window = _with_device_plane(recorded, tmp_path)
    s = scopes.summarize(str(path))
    assert s.window == window and s.chips == 1
    ns = 1e-9
    assert s.scope_s["asa.update"] == pytest.approx(200 * ns)
    assert s.scope_s["asa.read"] == pytest.approx(350 * ns)  # 300 + 50
    # the while's own loop control (600 - 200 - 100) and the unscoped op
    assert s.unscoped_s == pytest.approx(400 * ns)
    assert s.busy_s == pytest.approx(950 * ns)
    total = sum(s.scope_s.values()) + s.unscoped_s
    assert total == pytest.approx(s.busy_s, abs=1e-15)
    assert s.frac("asa.update") == pytest.approx(200 / 950)
    ops = {op: (sc, mod, t) for op, sc, mod, t in s.top_ops}
    assert ops["while.1"] == (None, "jit__update_body",
                              pytest.approx(300 * ns))
    assert s.program_s == pytest.approx({"jit__update_body": 600 * ns,
                                         "jit__read_decisions": 350 * ns})


def test_summary_reads_the_serve_phases(recorded, tmp_path):
    path, (w0, w1) = _with_device_plane(recorded, tmp_path)
    s = scopes.summarize(str(path))
    assert s.batches() >= 2                 # 6 requests, batches of 4
    for p in ("batch_form", "pad", "device_step", "scatter_read",
              "future_resolve"):
        assert len(s.serve[p]) == s.batches()
    for a, b in s.serve_in_window("future_resolve"):
        assert w0 <= a <= b <= w1
    assert scopes.covered_s([(0, 10), (5, 20), (30, 31)]) == \
        pytest.approx(21e-9)


def test_no_device_plane_reads_nothing(recorded):
    assert scopes.summarize(str(recorded)) is None
