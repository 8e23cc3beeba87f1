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

Batch semantics: observations scatter first, then every decision reads
the post-scatter table — a request that both observes and decides sees
its own update.  The host batcher (``repro.serve.loop``) guarantees **at
most one observation per slot per batch** (duplicates are deferred to
the next batch), which keeps the scatter well-defined; decisions are
pure reads, so duplicate decision slots are fine.

The ``mesh=`` path shard_maps the *query* axis over a 1-D ``scenarios``
mesh with the table replicated: each device updates its block of query
rows, all-gathers the updated rows, and applies the identical full-batch
scatter — so every device holds the same new table and the result is
bit-identical to the single-device vmap path (pinned by
tests/test_serve_sharded.py on 1/2/4/8 fake devices).

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


def _update_body(table: asa.ASAState, q: QueryBatch, mask: jax.Array,
                 scatter_rows=None) -> asa.ASAState:
    """Apply the batch's observations to the table.

    ``scatter_rows`` post-processes the locally-updated rows before the
    scatter — the sharded path all-gathers them so every device applies
    the identical full-batch write; the vmap path scatters them as-is.
    Its device work is named ``asa.update`` for the profiler.
    """
    m = table.log_p.shape[-1]
    n = table.log_p.shape[0]
    bins = jnp.asarray(make_bins(m), jnp.float32)
    with jax.named_scope("asa.update"):
        slot = jnp.clip(q.slot, 0, n - 1)

        # observations: gather each query's row, apply the tuned §4.5
        # update where the query carries one (learn_wait_if is a no-op —
        # PRNG included — on the False branch)
        rows = jax.tree.map(lambda x: x[slot], table)
        do = mask & q.has_obs
        upd = jax.vmap(asa.learn_wait_if, in_axes=(0, None, 0, 0))(
            rows, bins, q.observed_wait, do)

        # scatter the updated rows back; non-observing rows target index
        # n (mode="drop"), so only real observations touch the table
        tgt = jnp.where(do, slot, n)
        if scatter_rows is not None:
            tgt, upd = scatter_rows(tgt, upd)
        return jax.tree.map(
            lambda t, u: t.at[tgt].set(u, mode="drop"), table, upd)


_apply_updates = jax.jit(_update_body)


@jax.jit
def _read_decisions(table: asa.ASAState, q: QueryBatch) -> DecisionBatch:
    """Answer every query row from the (post-scatter) table.

    Deliberately its own compiled program, shared by the vmap and the
    shard_map paths: the posterior-mean ⟨p, θ⟩ is a float reduction, and
    XLA may vectorize the same reduction differently at different batch
    widths (a 1-ULP wiggle) — running the one full-batch program on the
    replicated table makes the sharded decisions bit-identical to the
    single-device ones by construction, not by luck. Its device work is
    named ``asa.read`` for the profiler.
    """
    m = table.log_p.shape[-1]
    n = table.log_p.shape[0]
    bins = jnp.asarray(make_bins(m), jnp.float32)
    with jax.named_scope("asa.read"):
        slot = jnp.clip(q.slot, 0, n - 1)
        fresh = jax.tree.map(lambda x: x[slot], table)
        feats = jax.vmap(asa.posterior_features, in_axes=(0, None))(
            fresh, bins)
        return DecisionBatch(
            lead_s=feats[:, 0], expected_s=feats[:, 1], entropy=feats[:, 2])


def decision_step(table: asa.ASAState, q: QueryBatch, mask: jax.Array
                  ) -> tuple[asa.ASAState, DecisionBatch]:
    """One batched decision step (single-device vmap path): scatter the
    observations, then answer every query from the post-scatter table —
    a query that both observes and decides sees its own update.

    ``mask`` is the validity mask from ``parallel.fleet.pad_batch`` —
    pad rows (copies of query 0) never update the table and their
    decision rows are garbage to be sliced off by the caller.
    """
    table = _apply_updates(table, q, mask)
    return table, _read_decisions(table, q)


@functools.lru_cache(maxsize=None)
def _sharded_update_fn(mesh):
    """Compiled shard_map of the update half for one mesh (cached, as
    ``xsim.events._sharded_sweep_fn`` caches its sweeps). Only the
    per-row posterior updates are sharded; the decision read runs in
    the shared ``_read_decisions`` program afterwards."""
    from repro.parallel import fleet as pfleet

    spec = pfleet.shard_spec()
    rep = pfleet.replicated_spec()

    def block(table: asa.ASAState, q: QueryBatch, mask: jax.Array):
        def gather_all(tgt, upd):
            # every device applies the FULL batch's scatter so the
            # replicated table stays identical everywhere — tiled
            # all_gather concatenates the blocks in mesh order, i.e. the
            # original batch order, so the write is bit-identical to the
            # single-device scatter
            tgt = jax.lax.all_gather(tgt, pfleet.SCENARIO_AXIS, tiled=True)
            upd = jax.tree.map(
                lambda x: jax.lax.all_gather(
                    x, pfleet.SCENARIO_AXIS, tiled=True), upd)
            return tgt, upd

        return _update_body(table, q, mask, scatter_rows=gather_all)

    fn = jax.shard_map(block, mesh=mesh, in_specs=(rep, spec, spec),
                       out_specs=rep, check_vma=False)
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
    table = _sharded_update_fn(mesh)(table, q, mask)
    return table, _read_decisions(table, q)
