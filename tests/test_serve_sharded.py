"""Sharded serving: shard_map decision step ≡ single-device vmap, bit
for bit — new tables (posteriors AND PRNG keys) and decision batches —
on 1/2/4/8 shards, plus server-level durability through the sharded
path.  CI's ``xsim-sharded`` job fakes 8 CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_scenarios_mesh
from repro.parallel import fleet as pfleet
from repro.serve import asa as serve_asa
from repro.serve.loop import ASAServer, ServeConfig

N_DEV = len(jax.devices())

needs = pytest.mark.skipif  # readability alias for the device gates


def _query(n, seed=0):
    """A busy batch: repeated decision slots, and slots observed several
    times (the step folds them in row order)."""
    rng = np.random.default_rng(seed)
    slot = rng.integers(0, 12, n).astype(np.int32)
    return serve_asa.QueryBatch(
        slot=jnp.asarray(slot),
        observed_wait=jnp.asarray(
            rng.uniform(20.0, 3000.0, n).astype(np.float32)),
        has_obs=jnp.asarray(rng.random(n) < 0.7))


def _assert_tables_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_serve_step_sharded_bit_identical(k):
    if N_DEV < k:
        pytest.skip(f"needs {k} devices, have {N_DEV} (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    table = serve_asa.init_table(16, seed=3)
    q = _query(24)
    qp, mask = pfleet.pad_batch(q, 32)          # 32 % k == 0 for all k
    ref_t, ref_d = serve_asa.serve_step(table, qp, mask)
    sh_t, sh_d = serve_asa.serve_step(table, qp, mask,
                                      mesh=make_scenarios_mesh(k))
    _assert_tables_equal(ref_t, sh_t)
    for la, lb in zip(ref_d, sh_d):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@needs(N_DEV < 2, reason="needs ≥2 devices")
def test_sharded_steps_compose_bit_identical():
    """A whole *sequence* of sharded steps stays bitwise on the vmap
    trajectory (the replicated table never drifts across steps)."""
    mesh = make_scenarios_mesh(2)
    ref = sh = serve_asa.init_table(16, seed=1)
    for step in range(4):
        q = _query(24, seed=step)
        qp, mask = pfleet.pad_batch(q, 32)
        ref, _ = serve_asa.serve_step(ref, qp, mask)
        sh, _ = serve_asa.serve_step(sh, qp, mask, mesh=mesh)
        _assert_tables_equal(ref, sh)


@needs(N_DEV < 2, reason="needs ≥2 devices")
def test_sharded_server_matches_vmap_server():
    """Two full servers — one vmap, one shard_map — fed identical
    request streams answer identical decisions."""
    cfg_v = ServeConfig(n_slots=16, batch_size=8)
    cfg_s = ServeConfig(n_slots=16, batch_size=8, n_shards=2)
    sv, ss = ASAServer(cfg_v), ASAServer(cfg_s)
    rng = np.random.default_rng(9)
    for _ in range(6):
        reqs = [(int(rng.integers(0, 10)),
                 float(rng.uniform(20, 2000))
                 if rng.random() < 0.6 else None)
                for _ in range(6)]
        fa = [sv.submit(t, w) for t, w in reqs]
        fb = [ss.submit(t, w) for t, w in reqs]
        while any(not f.done() for f in fa):
            sv.step_once(wait_s=0)
        while any(not f.done() for f in fb):
            ss.step_once(wait_s=0)
        for a, b in zip(fa, fb):
            da, db = a.result(timeout=10), b.result(timeout=10)
            assert (da.lead_s, da.expected_s, da.entropy) == \
                   (db.lead_s, db.expected_s, db.entropy)
    _assert_tables_equal(sv._table, ss._table)


@needs(N_DEV < 2, reason="needs ≥2 devices")
def test_sharded_restart_bitwise(tmp_path):
    """Durability through the sharded path: save under shard_map
    serving, restore, and both servers continue bitwise identically."""
    cfg = ServeConfig(n_slots=16, batch_size=8, n_shards=2,
                      checkpoint_dir=str(tmp_path / "ckpt"))
    server = ASAServer(cfg)
    rng = np.random.default_rng(2)
    for _ in range(5):
        fut = server.submit(int(rng.integers(0, 6)),
                            float(rng.uniform(20, 2000)))
        server.step_once(wait_s=0)
        fut.result(timeout=10)
    server.save(step=1)
    restored = ASAServer.restore(cfg, step=1)
    # range(8) admits tenants NEITHER server has seen: post-restart
    # admissions (dirty mask + reset-key salt were checkpointed) must
    # also line up bitwise with the uninterrupted server's
    for t in range(8):
        fa = server.submit(t, observed_wait=444.0)
        fb = restored.submit(t, observed_wait=444.0)
        server.step_once(wait_s=0)
        restored.step_once(wait_s=0)
        a, b = fa.result(timeout=10), fb.result(timeout=10)
        assert (a.lead_s, a.expected_s, a.entropy) == \
               (b.lead_s, b.expected_s, b.entropy)
    _assert_tables_equal(server._table, restored._table)


FOUR = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_scenarios_mesh
    from repro.parallel import fleet as pfleet
    from repro.serve import asa as serve_asa

    assert len(jax.devices()) == 4
    mesh = make_scenarios_mesh(4)
    table = serve_asa.init_table(16, seed=4)
    slot = np.array([2] * 6 + [5, 2, 7, 5, 2, 9] * 3, np.int32)
    q = serve_asa.QueryBatch(
        slot=jnp.asarray(slot),
        observed_wait=jnp.asarray(np.linspace(30.0, 4000.0, slot.size,
                                              dtype=np.float32)),
        has_obs=jnp.asarray(np.arange(slot.size) % 4 != 3))
    qp, mask = pfleet.pad_batch(q, 32)
    ref_t, ref_d = serve_asa.serve_step(table, qp, mask)
    sh_t, sh_d = serve_asa.serve_step(table, qp, mask, mesh=mesh)
    for a, b in zip(jax.tree.leaves((ref_t, ref_d)),
                    jax.tree.leaves((sh_t, sh_d))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("bit-identical")
""")


def test_sharded_fold_bit_identical_on_four_devices():
    """A batch observing one slot up to 10 times, on four virtual
    devices in a child process: the shard_map fold writes the vmap
    fold's table and answers bit for bit."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "bit-identical" in p.stdout
