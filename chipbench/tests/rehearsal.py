"""A benchmark root of tiny cells, for rehearsing a run on the CPU.

``make_root(tmp)`` writes ``BENCHMARK.json`` and the tiny cells' files
under ``tmp``, as a later PR would add a cell: data files only, with the
per-layer readers copied from the real ones.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "chipbench"


def tiny_configs() -> dict:
    up = json.loads((BENCH / "configs" / "uppmax.json").read_text())
    hp = json.loads((BENCH / "configs" / "hpc2n.json").read_text())
    for c in (up, hp):
        c["shrink"] = 1.0 / 64.0
        c["xsim"].update(n_warm=48, n_backlog=32, n_arrivals=64,
                         step_budget=2 * (48 + 32 + 64 + 9) + 2 * 9 + 16)
    hp["serve"].update(n_slots=256, batch_size=16, tenants=64)
    return {"tiny_uppmax": up, "tiny_hpc2n": hp}


def tiny_traffic() -> dict:
    sweep = json.loads((BENCH / "traffic" / "sweep.json").read_text())
    decide = json.loads((BENCH / "traffic" / "decide.json").read_text())
    decide.update(rate_per_s=300.0, check_tenants=32)
    x4 = json.loads((BENCH / "traffic" / "sweep_x4.json").read_text())
    return {"tiny_sweep": sweep, "tiny_decide": decide, "tiny_sweep_x4": x4}


def make_root(tmp: Path, extra_metric: str | None = None) -> Path:
    """A root holding tiny.sweep and tiny.decide. ``extra_metric`` adds a
    per-layer metric of that name (file and entry), for the discovery
    test."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    root = Path(tmp)
    for sub in ("configs", "traffic", "metrics"):
        (root / "chipbench" / sub).mkdir(parents=True, exist_ok=True)
    for name, c in tiny_configs().items():
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(c))
    for name, t in tiny_traffic().items():
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    for f in (BENCH / "metrics").glob("*.py"):
        shutil.copy(f, root / "chipbench" / "metrics" / f.name)
    bench["configs"] = [
        {"name": n, "source": "https://arxiv.org/abs/2401.09733",
         "file": f"chipbench/configs/{n}.json", "reduced": [], "why": "tiny"}
        for n in tiny_configs()]
    bench["workloads"] = [
        {"name": "tiny.sweep", "config": "tiny_uppmax",
         "traffic": "tiny_sweep", "chips": 1, "why": "tiny"},
        {"name": "tiny.decide", "config": "tiny_hpc2n",
         "traffic": "tiny_decide", "chips": 1, "why": "tiny"},
        {"name": "tiny.sweep.x4", "config": "tiny_uppmax",
         "traffic": "tiny_sweep_x4", "chips": 4, "why": "tiny"}]
    sweeps, dec = ["tiny.sweep", "tiny.sweep.x4"], ["tiny.decide"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sweeps if m["name"].startswith("sweep") \
                else dec
    if extra_metric:
        bench["per_layer"].append(
            {"name": extra_metric, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "lock-step loop",
             "moves": "sweep_scenarios_per_s", "workloads": sweeps})
        (root / "chipbench" / "metrics" / f"{extra_metric}.py").write_text(
            "def read(ctx):\n    return len(ctx['sweep_calls'])\n")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
