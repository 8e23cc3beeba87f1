"""CPU rehearsal of the Zipf-skewed decide cell (run by explicit path):

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

A tiny ``hpc2n_zipf.decide`` is added to the rehearsal root as data
files and entries, as ``rehearsal.make_root`` adds its cells.
"""

import importlib.util
import json

import numpy as np
import pytest

from chipbench import run
from chipbench.drivers.decide_zipf import check_sample, zipf_requests
from chipbench.tests.rehearsal import BENCH, REPO, make_root, tiny_configs


def tiny_zipf():
    cfg = json.loads((BENCH / "configs" / "hpc2n_zipf.json").read_text())
    cfg["serve"] = tiny_configs()["tiny_hpc2n"]["serve"]
    cfg["tenant_skew"]["rehot_s"] = 0.25
    traffic = json.loads((BENCH / "traffic" / "decide_zipf.json")
                         .read_text())
    traffic.update(rate_per_s=300.0, check_tenants=16, check_hot=4)
    return cfg, traffic


def make_zipf_root(tmp):
    root = make_root(tmp)
    cfg, traffic = tiny_zipf()
    (root / "chipbench" / "configs" / "tiny_hpc2n_zipf.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny_decide_zipf.json").write_text(
        json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny_hpc2n_zipf", "source": cfg["source"],
         "file": "chipbench/configs/tiny_hpc2n_zipf.json", "reduced": [],
         "why": "tiny"})
    bench["workloads"].append(
        {"name": "tiny_zipf.decide", "config": "tiny_hpc2n_zipf",
         "traffic": "tiny_decide_zipf", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"].startswith("decide"):
            m["workloads"].append("tiny_zipf.decide")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_zipf_draw_is_seeded_and_skewed():
    cfg, traffic = tiny_zipf()
    a, order_a = zipf_requests(traffic, cfg, 2**33 + 5, 2.0)
    b, order_b = zipf_requests(traffic, cfg, 2**33 + 5, 2.0)
    c, _ = zipf_requests(traffic, cfg, 2**33 + 6, 2.0)
    assert (a.tenant == b.tenant).all() and (a.due_s == b.due_s).all()
    assert all((x == y).all() for x, y in zip(order_a, order_b))
    assert not (a.tenant == c.tenant).all()
    # due times, waits and observations are the uniform generator's
    from chipbench.generator import requests
    base = requests(traffic, cfg, 2**33 + 5, 2.0)
    assert (a.due_s == base.due_s).all()
    np.testing.assert_array_equal(a.wait_s, base.wait_s)
    # within an epoch the hottest tenant sends far more than its share
    first = a.tenant[a.due_s < 0.25]
    top = np.bincount(first, minlength=64).max()
    assert top >= 3 * first.size / 64


def test_hot_set_moves_every_epoch_and_is_checked():
    cfg, traffic = tiny_zipf()
    reqs, order = zipf_requests(traffic, cfg, 77, 2.0)
    assert len(order) == int(2.0 // 0.25) + 1
    hottest = [perm[0] for perm in order]
    assert len(set(hottest)) > 1
    for e, perm in enumerate(order):
        at = (reqs.due_s // 0.25).astype(int) == e
        # each epoch's tenants follow its own permutation of the ranks
        assert np.isin(reqs.tenant[at], perm).all()
    sample = check_sample(traffic, cfg, 77, order)
    for perm in order:
        assert np.isin(perm[:4], sample).all()
    assert np.unique(sample).size == sample.size


def _reader(name):
    path = REPO / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_fold_readers_read_a_synthetic_ctx():
    before = {"asa_serve_folded_total": 5, "asa_serve_fold_rounds_total": 40,
              "asa_serve_batches_total": 30}
    after = {"asa_serve_folded_total": 25, "asa_serve_fold_rounds_total": 90,
             "asa_serve_batches_total": 50}
    ctx = {"counters": (before, after), "n_requests": 400}
    assert _reader("decide.folded_frac")(ctx) == pytest.approx(20 / 400)
    assert _reader("decide.fold_rounds")(ctx) == pytest.approx(50 / 20)
    # a program without the counters: no reading, no error
    old = {"asa_serve_batches_total": 3}
    ctx = {"counters": (old, old), "n_requests": 400}
    assert _reader("decide.folded_frac")(ctx) is None
    assert _reader("decide.fold_rounds")(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_zipf_cell_runs_and_is_correct(tmp_path, capsys, trace):
    root = make_zipf_root(tmp_path)
    rc = run.main(["--workload", "tiny_zipf.decide", "--seed",
                   "3123456789012", "--seconds", "1", "--trace", str(trace)],
                  root=root, require_tpu=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    if trace:
        m = res["metrics"]
        assert m["decide.folded_frac"]["value"] > 0
        assert m["decide.fold_rounds"]["value"] > 1
        assert m["decide.defer_frac"]["value"] == 0
    else:
        assert {"decide_p50_ms", "decide_p95_ms", "decide_per_s",
                "setup_s"} <= set(res["metrics"])
