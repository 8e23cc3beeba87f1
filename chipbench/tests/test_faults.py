"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program for the run only, the chip check is
skipped, and the rest of the run is the benchmark's own: set-up, window,
reference comparison. The four-chip faults run on four virtual CPU
devices in a child process.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.tests.rehearsal import REPO, make_root


def _sweep_faults(events, real):
    def unchanged(states, **kw):
        return states

    def half(states, **kw):
        out = real(states, **kw)
        n = states.status.shape[0] // 2
        return jax.tree.map(lambda o, i: jnp.concatenate([o[:n], i[n:]]),
                            out, states)

    def altered(states, **kw):
        out = real(states, **kw)
        return out._replace(start=out.start.at[:, -9].add(1.0))

    return {"unchanged": unchanged, "half_batch": half, "altered": altered}


def _decide_faults(serve_asa):
    real_upd = serve_asa._apply_updates
    real_read = serve_asa.decisions_to_host

    def unchanged(table, q, mask):
        return table

    def half(table, q, mask):
        # the live rows' second half is left out (a batch of one loses
        # its only row), whatever the padded width
        keep = jnp.cumsum(mask) <= jnp.sum(mask) // 2
        return real_upd(table, q, mask & keep)

    def altered(dec):
        lead, expected, entropy = real_read(dec)
        lead = lead.copy()
        lead[0] += 1.0
        return lead, expected, entropy

    return {"unchanged": ("_apply_updates", unchanged),
            "half_batch": ("_apply_updates", half),
            "altered": ("decisions_to_host", altered)}


def _run(root, capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "424242424242",
                   "--seconds", "1", "--trace", "0"], root=root,
                  require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_sweep_fault_is_caught(tmp_path, capsys, monkeypatch, fault):
    from repro.xsim import events

    monkeypatch.setattr(events, "sweep",
                        _sweep_faults(events, events.sweep)[fault])
    rc, res = _run(make_root(tmp_path), capsys, "tiny.sweep")
    assert rc == 0 and res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_decide_fault_is_caught(tmp_path, capsys, monkeypatch, fault):
    from repro.serve import asa as serve_asa

    name, fn = _decide_faults(serve_asa)[fault]
    monkeypatch.setattr(serve_asa, name, fn)
    rc, res = _run(make_root(tmp_path), capsys, "tiny.decide")
    assert rc == 0 and res["correct"] is False, res["checks"]


X4 = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    import jax, jax.numpy as jnp
    from chipbench import run
    from chipbench.tests.rehearsal import make_root
    from repro.xsim import events
    real = events.sharded_sweep

    def no_exchange(states, **kw):
        # each chip's block is computed, but only chip 0's comes back
        out = real(states, **kw)
        n = states.status.shape[0] // 4
        return jax.tree.map(
            lambda o, i: jnp.concatenate([o[:n], i[n:]]), out, states)

    if sys.argv[2] == "no_exchange":
        events.sharded_sweep = no_exchange
    root = make_root(Path(sys.argv[1]))
    sys.exit(run.main(["--workload", "tiny.sweep.x4", "--seed", "77",
                       "--seconds", "1", "--trace", "0"], root=root,
                      require_tpu=False))
""")


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_four_chip_sweep(tmp_path, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", X4, str(tmp_path), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is correct, res["checks"]
    assert np.isfinite(res["metrics"]["sweep_scenarios_per_s"]["value"])
