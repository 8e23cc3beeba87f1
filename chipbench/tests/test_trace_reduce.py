"""The trace reduction, on a small trace recorded on this CPU: a jitted
sort under ``chipbench.run_grid`` inside ``chipbench.window``. The CPU
backend runs its operations on host threads and has no device plane, so
the reduction must find nothing to read there and return None, which
leaves every device metric out of the result line."""

from pathlib import Path

from jax.profiler import ProfileData

from chipbench import trace_reduce

DATA = Path(__file__).parent / "data"


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]


def test_sort_ops_are_recognised():
    assert trace_reduce.is_sort("sort.12", {})
    assert trace_reduce.is_sort("fusion.3", {"long_name": "%sort.4 = ..."})
    assert not trace_reduce.is_sort("fusion.3", {"hlo_category": "loop"})


def test_cpu_trace_has_no_device_time():
    path = DATA / "tiny_cpu.xplane.pb"
    pd = ProfileData.from_file(str(path))
    names = {e.name for p in pd.planes if p.name.startswith("/host")
             for ln in p.lines for e in ln.events}
    assert {"chipbench.window", "chipbench.run_grid"} <= names
    assert trace_reduce.reduce(str(path)) is None


def test_latest_xplane(tmp_path):
    assert trace_reduce.latest_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(b"")
    assert trace_reduce.latest_xplane(str(tmp_path)).endswith("h.xplane.pb")
