"""Algorithm 1 unit + property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import asa
from repro.core.bins import make_bins, nearest_bin
from repro.core.losses import asymmetric, log_distance, zero_one


def test_init_uniform():
    s = asa.init(53, jax.random.PRNGKey(0))
    p = np.asarray(s.p)
    assert p.shape == (53,)
    np.testing.assert_allclose(p, 1.0 / 53, rtol=1e-6)


def test_bins_paper_grid():
    b = make_bins(53)
    assert b.shape == (53,)
    assert b[0] == 10.0 and b[-1] == 100_000.0
    assert np.all(np.diff(b) > 0)
    # §4.3: density skewed to the 10s/100s decades
    assert np.sum(b < 1000) > 40


def test_nearest_bin_roundtrip():
    b = make_bins(53)
    for i in (0, 7, 20, 52):
        assert nearest_bin(b, b[i]) == i


@given(st.integers(min_value=2, max_value=97))
@settings(max_examples=10, deadline=None)
def test_bins_other_m(m):
    b = make_bins(m)
    assert b.shape == (m,)
    assert np.all(np.diff(b) > 0)


def test_update_keeps_distribution():
    s = asa.init(8, jax.random.PRNGKey(1))
    g = jnp.float32(1.0)
    for i in range(20):
        lv = zero_one(jnp.asarray(make_bins(8), jnp.float32),
                      jnp.float32(10.0 * (i + 1)))
        s, a = asa.step(s, lv, g, policy="default")
        p = np.asarray(s.p)
        np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-5)
        assert np.all(p >= 0)


def test_round_closes_only_past_unit_loss():
    """Inner loop runs while max_a ℓ_ta ≤ 1 (Algorithm 1 line 3)."""
    s = asa.init(4, jax.random.PRNGKey(0))
    g = jnp.float32(1.0)
    # loss 1 on action 0: first observe -> max ℓ == 1 -> round NOT closed
    s1 = asa.observe(s, jnp.int32(0), jnp.float32(1.0), g)
    assert int(s1.rounds) == 0
    # second unit loss on same action -> max ℓ == 2 > 1 -> round closes
    s2 = asa.observe(s1, jnp.int32(0), jnp.float32(1.0), g)
    assert int(s2.rounds) == 1
    assert float(jnp.max(s2.round_loss)) == 0.0  # reset


def test_tuned_sharpens_on_truth():
    bins = jnp.asarray(make_bins(53), jnp.float32)
    s = asa.init(53, jax.random.PRNGKey(2))
    truth = 500.0
    g = jnp.float32(1.0)
    for _ in range(30):
        lv = zero_one(bins, jnp.float32(truth))
        s, _ = asa.step(s, lv, g, policy="tuned", repetitions=50)
    est = float(asa.map_wait(s, bins))
    assert abs(np.log(est) - np.log(truth)) < 0.3


def test_greedy_vs_default_convergence():
    from repro.core.convergence import simulate
    truth = np.full(300, 1000.0, dtype=np.float32)
    r_tuned = simulate("tuned", T=300, truth=truth, seed=5)
    assert r_tuned.hit[-50:].mean() > 0.5
    # estimates end near the truth
    assert abs(np.log(r_tuned.estimate[-1]) - np.log(1000.0)) < 0.5


@given(st.floats(min_value=10.0, max_value=1e5))
@settings(max_examples=20, deadline=None)
def test_losses_bounded(w):
    bins = jnp.asarray(make_bins(53), jnp.float32)
    for fn in (zero_one, log_distance, asymmetric):
        lv = np.asarray(fn(bins, jnp.float32(w)))
        assert lv.shape == (53,)
        assert np.all(lv >= 0) and np.all(lv <= 1.0 + 1e-6)
    # zero_one has exactly one zero
    assert int(np.sum(np.asarray(zero_one(bins, jnp.float32(w))) == 0)) == 1


def test_batched_estimators_independent():
    s = asa.init_batch(8, 3, jax.random.PRNGKey(0))
    bins = jnp.asarray(make_bins(8), jnp.float32)
    lv = jax.vmap(lambda w: zero_one(bins, w))(
        jnp.asarray([10.0, 1000.0, 100000.0], jnp.float32))
    for _ in range(30):
        s, _ = asa.batched_step(s, lv, jnp.float32(1.0))
    maps = jax.vmap(lambda st: asa.map_wait(st, bins))(s)
    est = np.asarray(maps)
    assert est[0] < est[1] < est[2]


@pytest.mark.parametrize("waits", [(500.0, 2000.0), (2000.0, 500.0),
                                   (30.0, 40.0), (40.0, 30.0)])
def test_map_is_plain_argmax_on_near_ties(waits):
    """Two observations in different bins leave those bins tied, 1 nat
    above the rest; the f32 update leaves them an ULP or two apart, the
    order of the observations deciding which is larger. The MAP is the
    posterior's argmax all the same: no tie rule, whichever bin rounding
    put on top, and never the bin a whole nat below."""
    bins = jnp.asarray(make_bins(53), jnp.float32)
    s = asa.init(53, jax.random.PRNGKey(0))
    for w in waits:
        s = asa.observe_full(s, zero_one(bins, jnp.float32(w)),
                             jnp.float32(1.0 / 50), 50)
    pair = sorted(nearest_bin(np.asarray(bins), waits).tolist())
    log_p = np.asarray(s.log_p)
    assert abs(log_p[pair[0]] - log_p[pair[1]]) < 1e-5
    top = int(np.argmax(log_p))
    assert top in pair
    assert int(asa.greedy_action(s)) == top
    assert float(asa.map_wait(s, bins)) == float(bins[top])
    s1 = asa.observe_full(s, zero_one(bins, jnp.float32(max(waits))),
                          jnp.float32(1.0 / 50), 50)
    assert int(asa.greedy_action(s1)) == pair[1]
