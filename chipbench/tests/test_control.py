"""The control (the reference in bfloat16, in the program's place) comes
out as not correct, at a size a test run holds."""

import jax
import pytest

from chipbench import control, run
from chipbench.tests.rehearsal import make_root


@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.decide"])
def test_control_is_not_correct(tmp_path, workload):
    cell, cfg, traffic, _, _ = run.load_cell(make_root(tmp_path), workload)
    for seed in (1, 2, 3):
        if traffic["kind"] == "sweep":
            c = control.sweep_control(cfg, traffic, seed, jax.devices())
        else:
            c = control.decide_control(cfg, traffic, seed, 2.0)
        assert not c.ok, c.as_dict()
