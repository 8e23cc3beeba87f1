"""CPU rehearsals of a benchmark run (run by explicit path):

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Each traffic kind runs end to end at a tiny size; a cell, configuration,
traffic mix and per-layer metric added as files and entries alone are
found by name; and the real command refuses to run without a TPU.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import common, generator, run
from chipbench.tests.rehearsal import REPO, make_root, tiny_configs, \
    tiny_traffic


def run_cell(root, capsys, workload, seed=123456789012, seconds=1.0,
             trace=0):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.decide"])
def test_traffic_runs_and_is_correct(tmp_path, capsys, workload):
    rc, res = run_cell(make_root(tmp_path), capsys, workload)
    assert rc == 0
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


def test_generator_is_seeded():
    cfg = tiny_configs()["tiny_hpc2n"]
    t = tiny_traffic()["tiny_decide"]
    a = generator.requests(t, cfg, 2**33 + 5, 2.0)
    b = generator.requests(t, cfg, 2**33 + 5, 2.0)
    c = generator.requests(t, cfg, 2**33 + 6, 2.0)
    assert (a.tenant == b.tenant).all() and (a.due_s == b.due_s).all()
    assert not (a.tenant == c.tenant).all()
    assert a.due_s.shape == c.due_s.shape       # same work, other order
    assert a.due_s[0] == 0.0 and abs(a.due_s[-1] - 2.0) < 1e-9
    s = tiny_traffic()["tiny_sweep"]
    calls = [generator.sweep_call(s, 7, k) for k in range(4)]
    assert [c.workflow for c in calls] == ["montage", "blast", "statistics",
                                           "montage"]
    assert len({c.grid_seed for c in calls}) == 4


def test_added_files_are_found_by_name(tmp_path, capsys):
    """A metric added as a file and an entry, with no file edited, is
    read in a traced run of a cell added the same way."""
    root = make_root(tmp_path, extra_metric="sweep.calls_in_window")
    rc, res = run_cell(root, capsys, "tiny.sweep", trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["sweep.calls_in_window"]["value"] >= 1
    assert res["metrics"]["sweep.lane_busy_frac"]["unit"] == "fraction"


def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "uppmax.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_peaks_by_device_kind():
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("cpu")
