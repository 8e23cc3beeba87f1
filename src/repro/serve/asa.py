"""Live batched ASA decisions: the jitted core of ASA-as-a-service.

The paper's whole point is *proactive* submission — ASA estimates the
queue wait a_y for the next stage and submits it a_y seconds before the
current stage's expected end (§3, Alg. 1).  This module answers that
question as a service: one jitted **decision step** serves a padded batch
of per-tenant queries against a fixed-slot **tenant table** of
device-resident Algorithm-1 posteriors (a batched ``core.asa.ASAState``,
one row per tenant slot).

A query carries (slot, observed_wait?, has_obs):

* **observe** — the tenant saw a stage actually start after
  ``observed_wait`` seconds in the queue.  The slot's posterior takes the
  tuned §4.5 update (``asa.learn_wait_if`` — the exact update the xsim
  engine threads through its scan), consuming the slot's own PRNG key.
* **decide** — every query row answers "how far ahead should the next
  stage be submitted": the MAP wait of the (freshly updated) posterior,
  plus the posterior-mean wait and entropy (``asa.posterior_features``).

Batch semantics — **serial equivalence**: every answer of a batch
equals what the server would give if it served the batch's requests one
at a time, in row (= arrival) order.  A request that observes reads its
slot's posterior after its own observation and every earlier one of its
slot; a decide-only request reads it after the observations of its slot
that precede it.  Several observations of one slot in a batch are
**folded in order**: the step ranks each observing row within its slot
by row order and applies rank r in round r, so every round updates
unique slots and consumes each slot's PRNG key once per observation, as
a sequential chain (``asa.learn_wait_if`` is not a sum).  The number of
rounds is the largest count of observations any slot has in the batch,
a loop trip count read from the data: nothing recompiles per batch, and
a batch with no repeated observing slot runs the one round it always
did.  The fold runs once: the update step keeps the state each row
answers from, and the read computes every answer from those rows.

The ``mesh=`` path shard_maps each round's per-row updates over a 1-D
``scenarios`` mesh with the table and the batch replicated: each device
updates its block of rows, all-gathers the updated rows, and every
device applies the identical full-batch scatter — so every device holds
the same new table and answer rows, and the result is bit-identical to
the single-device vmap path (pinned by tests/test_serve_sharded.py).

Everything here is pure/functional; threads, queues, tenant admission
and checkpoint cadence live in ``repro.serve.loop``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import asa
from repro.core.bins import make_bins


class ServeStepError(RuntimeError):
    """One batch's jitted decision step failed.

    The serve loop raises this INTO the batch's futures — containment is
    per batch, the loop itself survives (``__cause__`` carries the device
    exception; ``batch`` the dispatched-batch index).  Clients retry; the
    tenant table holds its pre-dispatch state when the failure happened
    at dispatch (the functional update never landed)."""

    def __init__(self, msg: str, *, batch: int = -1):
        super().__init__(msg)
        self.batch = batch


class QueryBatch(NamedTuple):
    """One padded batch of tenant queries (all leaves shaped (B,))."""

    slot: jax.Array           # i32 tenant-table slot per query
    observed_wait: jax.Array  # f32 observed queue wait (seconds)
    has_obs: jax.Array        # bool: this query carries an observation


class DecisionBatch(NamedTuple):
    """Per-query answers (all (B,)); rows where the pad mask is False
    are computed against slot 0's copies and must be discarded."""

    lead_s: jax.Array      # MAP wait: the submit-lead-time ASA acts on
    expected_s: jax.Array  # posterior-mean wait ⟨p, θ⟩
    entropy: jax.Array     # Shannon entropy of p (how much ASA hedges)


def init_table(n_slots: int, m: int = 53, seed: int = 0) -> asa.ASAState:
    """The fixed-slot tenant table: ``n_slots`` independent Algorithm-1
    estimators with per-slot PRNG keys (a batched ``ASAState``)."""
    return asa.init_batch(m, n_slots, jax.random.PRNGKey(seed))


@jax.jit
def reset_slot(table: asa.ASAState, slot: jax.Array,
               key: jax.Array) -> asa.ASAState:
    """Re-initialise one slot (tenant eviction → slot reuse): the row
    returns to the uniform p_0 = 1/m prior with a fresh PRNG key."""
    m = table.log_p.shape[-1]
    fresh = asa.init(m, key)
    return jax.tree.map(lambda t, f: t.at[slot].set(f), table, fresh)


class _Fold(NamedTuple):
    """Per-row bookkeeping of one batch's in-order fold (all (B,))."""

    slot: jax.Array    # i32 table row of each query (clipped)
    do: jax.Array      # the row carries a live observation
    rank: jax.Array    # observations of its slot in rows before it
    prev: jax.Array    # the last of those rows, else the row itself
    last: jax.Array    # no later row observes its slot (observers scatter)
    rounds: jax.Array  # () rounds to run: observations of the busiest slot


def _plan(q: QueryBatch, mask: jax.Array, n: int) -> _Fold:
    """Rank every row against the observing rows of its slot (B²
    compares: the batch is a few hundred rows, the table tens of
    thousands)."""
    slot = jnp.clip(q.slot, 0, n - 1)
    do = mask & q.has_obs
    i = jnp.arange(slot.shape[0])
    same = (slot[:, None] == slot[None, :]) & do[None, :]  # [row, j]
    before = same & (i[None, :] < i[:, None])
    rank = jnp.sum(before, axis=1, dtype=jnp.int32)
    prev = jnp.max(jnp.where(before, i[None, :], -1), axis=1)
    later = jnp.any(same & (i[None, :] > i[:, None]), axis=1)
    return _Fold(slot=slot, do=do, rank=rank,
                 prev=jnp.where(prev < 0, i, prev), last=do & ~later,
                 rounds=jnp.maximum(jnp.max(jnp.where(do, rank + 1, 0)), 1))


_learn_rows = jax.vmap(asa.learn_wait_if, in_axes=(0, None, 0, 0))


def _fold(table: asa.ASAState, q: QueryBatch, plan: _Fold,
          learn=_learn_rows) -> asa.ASAState:
    """Each row's state after its own observation (observers) or its
    pre-batch row (decide-only rows): round r applies the rank-r
    observations to the state their previous observer left in round
    r - 1.

    ``learn(rows, bins, waits, do)`` updates every row of the batch
    where ``do`` holds (``learn_wait_if`` is a no-op, PRNG included, on
    the False branch); the sharded path splits it over the mesh."""
    bins = jnp.asarray(make_bins(table.log_p.shape[-1]), jnp.float32)
    rows = jax.tree.map(lambda x: x[plan.slot], table)
    # round 0 updates each slot's first observer from its own row
    rows = learn(rows, bins, q.observed_wait, plan.do & (plan.rank == 0))

    def round_r(r, cur):
        on = plan.do & (plan.rank == r)
        upd = learn(jax.tree.map(lambda x: x[plan.prev], cur), bins,
                    q.observed_wait, on)
        return jax.tree.map(
            lambda u, c: jnp.where(on.reshape((-1,) + (1,) * (u.ndim - 1)),
                                   u, c), upd, cur)

    return jax.lax.fori_loop(1, plan.rounds, round_r, rows)


def _update_body(table: asa.ASAState, q: QueryBatch, mask: jax.Array,
                 learn=_learn_rows):
    """Fold the batch's observations once. Returns the new table (each
    slot's state is that of its last observer, one unique-slot
    scatter), and the fold's rows with the row each query answers from.
    Its device work is named ``asa.update`` for the profiler."""
    n = table.log_p.shape[0]
    with jax.named_scope("asa.update"):
        plan = _plan(q, mask, n)
        rows = _fold(table, q, plan, learn)
        # only each slot's last observer writes; the other rows target
        # index n (mode="drop") and never touch the table
        tgt = jnp.where(plan.last, plan.slot, n)
        new = jax.tree.map(
            lambda t, u: t.at[tgt].set(u, mode="drop"), table, rows)
        # an observer answers from its own state, a decide-only row from
        # its slot's last observer before it (or its pre-batch row)
        src = jnp.where(plan.do, jnp.arange(tgt.shape[0]), plan.prev)
        return new, rows, src


_apply_updates = jax.jit(_update_body)


@jax.jit
def _read_decisions(rows: asa.ASAState, src: jax.Array) -> DecisionBatch:
    """Answer every query row from the fold's row ``src`` names.

    Deliberately its own compiled program, shared by the vmap and the
    shard_map paths: the posterior-mean ⟨p, θ⟩ is a float reduction, and
    XLA may vectorize the same reduction differently at different batch
    widths (a 1-ULP wiggle) — running the one full-batch program on the
    replicated rows makes the sharded decisions bit-identical to the
    single-device ones by construction, not by luck. Its device work is
    named ``asa.read`` for the profiler.
    """
    bins = jnp.asarray(make_bins(rows.log_p.shape[-1]), jnp.float32)
    with jax.named_scope("asa.read"):
        at = jax.tree.map(lambda x: x[src], rows)
        feats = jax.vmap(asa.posterior_features, in_axes=(0, None))(
            at, bins)
        return DecisionBatch(
            lead_s=feats[:, 0], expected_s=feats[:, 1], entropy=feats[:, 2])


def decision_step(table: asa.ASAState, q: QueryBatch, mask: jax.Array
                  ) -> tuple[asa.ASAState, DecisionBatch]:
    """One batched decision step (single-device vmap path): fold the
    observations into the table, and answer every query as if the
    batch were served one request at a time (module docstring).

    ``mask`` is the validity mask from ``parallel.fleet.pad_batch`` —
    pad rows (copies of query 0) never update the table and their
    decision rows are garbage to be sliced off by the caller.
    """
    table, rows, src = _apply_updates(table, q, mask)
    return table, _read_decisions(rows, src)


@functools.lru_cache(maxsize=None)
def _sharded_update_fn(mesh):
    """Compiled shard_map of the fold for one mesh (cached, as
    ``xsim.events._sharded_sweep_fn`` caches its sweeps). Only each
    round's per-row posterior updates are sharded; the decision read
    runs in the shared ``_read_decisions`` program afterwards."""
    from repro.parallel import fleet as pfleet

    rep = pfleet.replicated_spec()
    k = mesh.shape[pfleet.SCENARIO_AXIS]

    def learn_block(rows, bins, waits, do):
        # this device updates its block of rows; the tiled all_gather
        # concatenates the blocks in mesh order, i.e. batch order, so
        # every device continues from the identical full-batch rows
        size = do.shape[0] // k
        start = jax.lax.axis_index(pfleet.SCENARIO_AXIS) * size

        def block(x):
            return jax.lax.dynamic_slice_in_dim(x, start, size)

        upd = _learn_rows(jax.tree.map(block, rows), bins, block(waits),
                          block(do))
        return jax.tree.map(
            lambda x: jax.lax.all_gather(x, pfleet.SCENARIO_AXIS,
                                         tiled=True), upd)

    def body(table: asa.ASAState, q: QueryBatch, mask: jax.Array):
        return _update_body(table, q, mask, learn=learn_block)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(rep, rep, rep),
                       out_specs=(rep,) * 3, check_vma=False)
    return jax.jit(fn)


def decisions_to_host(dec: DecisionBatch
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bring a ``DecisionBatch`` to host in ONE device→host sync.

    ``np.asarray`` per field costs three round-trips to the device
    stream; ``jax.device_get`` on the whole tuple blocks once.  This is
    also the serve loop's *scatter-read* instrumentation point: the call
    blocks until the dispatched ``serve_step`` actually finishes, so the
    time spent here is the host-blocked device wait
    (``obs.serve_obs`` records it as the ``scatter_read`` span, distinct
    from the async ``device_step`` dispatch)."""
    lead, expected, entropy = jax.device_get(
        (dec.lead_s, dec.expected_s, dec.entropy))
    return np.asarray(lead), np.asarray(expected), np.asarray(entropy)


def serve_step(table: asa.ASAState, q: QueryBatch, mask: jax.Array, *,
               mesh=None) -> tuple[asa.ASAState, DecisionBatch]:
    """Dispatch one padded query batch: vmap path (``mesh=None``) or the
    bit-identical shard_map path over a 1-D ``scenarios`` mesh (build it
    with ``launch.mesh.make_scenarios_mesh``; the batch's leading axis
    must be divisible by the mesh size — ``loop.ServeConfig`` enforces
    ``batch_size % n_shards == 0``). Both paths answer through the one
    ``_read_decisions`` program, so equal tables give equal decisions
    bit for bit."""
    if mesh is None:
        return decision_step(table, q, mask)
    table, rows, src = _sharded_update_fn(mesh)(table, q, mask)
    return table, _read_decisions(rows, src)
