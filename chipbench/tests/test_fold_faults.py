"""A decide run with the decision step's fold broken comes out not correct.

Each fault wraps the program's one update step,
``serve.asa._apply_updates``, or its host read,
``serve.asa.decisions_to_host``, for the run only; the rest of the run
is the benchmark's own: set-up, window, reference comparison. The fold
faults run on the Zipf cell, whose check holds every answer to serial
equivalence; the table and read faults run on both decide cells.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests.rehearsal import make_root
from chipbench.tests.test_zipf_rehearsal import make_zipf_root


def _faults(serve_asa):
    real = serve_asa._apply_updates

    def unchanged(table, q, mask):
        _, rows, src = real(table, q, mask)
        return table, rows, src

    def half_batch(table, q, mask):
        # the live rows' second half is left out (a batch of one loses
        # its only row), whatever the padded width
        keep = jnp.cumsum(mask) <= jnp.sum(mask) // 2
        return real(table, q, mask & keep)

    def first_only(table, q, mask):
        # an observation behind an earlier one of its slot is dropped
        plan = serve_asa._plan(q, mask, table.log_p.shape[0])
        return real(table, q._replace(has_obs=q.has_obs & (plan.rank == 0)),
                    mask)

    def reversed_order(table, q, mask):
        # the batch folded last row first
        new, rows, src = real(table, jax.tree.map(lambda x: x[::-1], q),
                              mask[::-1])
        return (new, jax.tree.map(lambda x: x[::-1], rows),
                mask.shape[0] - 1 - src[::-1])

    def read_ahead(table, q, mask):
        # every row answers from its slot's state after the whole batch
        new, _, src = real(table, q, mask)
        slot = jnp.clip(q.slot, 0, table.log_p.shape[0] - 1)
        return (new, jax.tree.map(lambda x: x[slot], new),
                jnp.arange(src.shape[0]))

    return {"unchanged": unchanged, "half_batch": half_batch,
            "first_only": first_only, "reversed_order": reversed_order,
            "read_ahead": read_ahead}


def _run(root, capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "424242424242",
                   "--seconds", "1", "--trace", "0"], root=root,
                  require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "first_only",
                                   "reversed_order", "read_ahead"])
def test_zipf_fold_fault_is_caught(tmp_path, capsys, monkeypatch, fault):
    from repro.serve import asa as serve_asa

    monkeypatch.setattr(serve_asa, "_apply_updates",
                        _faults(serve_asa)[fault])
    rc, res = _run(make_zipf_root(tmp_path), capsys, "tiny_zipf.decide")
    assert rc == 0 and res["correct"] is False, res["checks"]
    assert res["failed"] == 0       # wrong answers, not failed requests


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_uniform_table_fault_is_caught(tmp_path, capsys, monkeypatch,
                                       fault):
    from repro.serve import asa as serve_asa

    monkeypatch.setattr(serve_asa, "_apply_updates",
                        _faults(serve_asa)[fault])
    rc, res = _run(make_root(tmp_path), capsys, "tiny.decide")
    assert rc == 0 and res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", ["tiny.decide", "tiny_zipf.decide"])
def test_altered_read_is_caught(tmp_path, capsys, monkeypatch, workload):
    from repro.serve import asa as serve_asa

    real = serve_asa.decisions_to_host

    def altered(dec):
        lead, expected, entropy = real(dec)
        lead = lead.copy()
        lead[0] += 1.0
        return lead, expected, entropy

    monkeypatch.setattr(serve_asa, "decisions_to_host", altered)
    rc, res = _run(make_zipf_root(tmp_path), capsys, workload)
    assert rc == 0 and res["correct"] is False, res["checks"]
