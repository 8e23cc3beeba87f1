"""Vectorized FCFS + EASY-backfill scheduling pass.

Mirrors ``QueueSim._schedule_pass`` with masked array ops:

  1. *FCFS prefix start* — eligible queued jobs sorted by (submit, row);
     because core counts are positive the "start from the front while it
     fits" loop is exactly the maximal prefix whose core cumsum fits in
     the free cores, so one sort + cumsum starts any number of head jobs.
  2. *Reservation* — when the queue head does not fit, compute its
     earliest feasible start (shadow time) and the spare cores at that
     moment. The default path computes both scalars directly: one
     key-value sort of the running jobs' (end, cores), a cores cumsum,
     the smallest end at which free + cumsum covers the head (a min
     reduction), and the cores of every job ending by then (a masked
     sum) — no gather, no binary search. The other modes compute the
     per-row vector freed[i] = Σ cores of running jobs ending ≤ end_i
     and read the reservation from it, as differential checks: the
     O(n²) pairwise comparison (``freed_mode="ref_n2"``) and a Pallas
     kernel (`freed_matrix`, ``"tpu"``/``"interpret"``) that runs the
     O(n log n) sorted formulation batched on the accelerator: XLA
     sorts the (B, N) tables, the kernel does the O(n) scan portion
     (cores cumsum + tie-aware backward fill) in VMEM, and the result
     scatters back through the inverse permutation. Its jnp reference,
     ``_freed_sorted``, gathers the cumsum at the last index of each
     end-time tie run. All of them agree bit-for-bit on the
     integer-valued core counts every grid uses (every sum is an exact
     integer below 2**24).
  3. *Backfill loop* — a short `fori_loop`; each pass starts the first
     (FCFS order) queued job that fits now AND either drains before the
     shadow time or fits inside the reservation's spare cores. QueueSim
     starts arbitrarily many backfill jobs per pass; a bounded loop is the
     vectorized approximation (any job missed here is reconsidered at the
     very next event, so with the default 16 passes the divergence is
     rarely observable).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.xsim.state import DONE, QUEUED, RUNNING, ScenarioState

BF_PASSES = 16  # backfill starts per scheduling pass (QueueSim: unbounded)

FREED_MODES = ("ref", "ref_n2", "interpret", "tpu")


# ---------------------------------------------------------------- helpers
def eligible_mask(s: ScenarioState) -> jax.Array:
    """Queued jobs whose afterok dependency (if any) has completed."""
    dep = jnp.clip(s.start_dep, 0, s.status.shape[0] - 1)
    dep_done = jnp.where(s.start_dep < 0, True, s.status[dep] == DONE)
    return (s.status == QUEUED) & dep_done


def fcfs_order(s: ScenarioState, mask: jax.Array):
    """Stable FCFS ordering of ``mask`` jobs by (submit, row index).

    Returns (order, rank): ``order`` lists job rows FCFS-first (masked-out
    rows pushed to the back), ``rank[j]`` is row j's queue position.
    """
    key = jnp.where(mask, s.submit, jnp.inf)
    order = jnp.argsort(key)                 # stable → row index tiebreak
    rank = jnp.argsort(order)
    return order, rank


# ------------------------------------------------- reservation (the scan)
def _freed_math(ends, cores, running):
    """O(n²) reference: freed[i] = cores released once every running job
    ending ≤ end_i ends. Kept behind ``freed_mode="ref_n2"`` so the
    sorted fast path can always be differentially checked against it."""
    e = jnp.where(running, ends, jnp.inf)
    c = jnp.where(running, cores, 0.0)
    before = (e[None, :] <= e[:, None]) & running[None, :]
    return jnp.sum(jnp.where(before, c[None, :], 0.0), axis=1)


def _freed_sorted(ends, cores, running):
    """O(n log n) freed-cores scan: argsort + cores-cumsum + tie gather.

    Sort the (masked) end times; the cores cumsum at sorted position k is
    the total released by the first k+1 enders, so freed[i] is the cumsum
    at the *last* sorted index whose end ≤ end_i — ``searchsorted(...,
    side="right") − 1`` lands exactly there, ties included. Non-running
    rows are masked to end=+inf / cores=0, reproducing the reference's
    convention (their freed value is the whole running total). Exact (not
    just close) for integer-valued core counts: both this cumsum and the
    reference's row-order sum are exact integer arithmetic below 2**24.
    """
    e = jnp.where(running, ends, jnp.inf)
    c = jnp.where(running, cores, 0.0)
    order = jnp.argsort(e)
    csum = jnp.cumsum(c[order])
    cnt = jnp.searchsorted(e[order], e, side="right")  # ≥ 1: e_i is present
    return csum[cnt - 1]


def _shifted(x, k: int, fill, *, left: bool):
    """``x`` moved ``k`` lanes along its last axis, ``fill`` shifted in:
    ``left`` gives x[:, i+k], otherwise x[:, i-k]. Static slices and a
    concat, which Mosaic lowers at any width."""
    pad = jnp.full((x.shape[0], k), fill, x.dtype)
    if left:
        return jnp.concatenate([x[:, k:], pad], axis=-1)
    return jnp.concatenate([pad, x[:, :-k]], axis=-1)


def _freed_sorted_kernel(ends_ref, cores_ref, freed_ref):
    """Scan portion of the sorted formulation, on PRE-SORTED (R, N) rows.

    freed_sorted[k] must be the cores cumsum at the last index of k's
    end-time tie run. With ``csum`` nondecreasing, that value is the
    minimum of ``csum`` over the run-*last* positions at or after k — a
    suffix-min over ``where(is_last, csum, +inf)``. Both scans are
    log₂(N)-step shift-and-combine doublings (Mosaic has no cumsum):
    shift-and-add for the prefix sum, shift-and-min for the suffix-min.
    The doubled sum is exact, hence equal to ``jnp.cumsum``: core counts
    are integers and every partial sum stays below 2**24.
    """
    e = ends_ref[...]                      # (R, N), each row ascending
    n = e.shape[-1]
    csum = cores_ref[...]
    k = 1
    while k < n:                           # static unroll: ⌈log₂ N⌉ steps
        csum = csum + _shifted(csum, k, 0.0, left=False)
        k *= 2
    is_last = e != _shifted(e, 1, -jnp.inf, left=True)  # end of tie run
    v = jnp.where(is_last, csum, jnp.inf)
    k = 1
    while k < n:
        v = jnp.minimum(v, _shifted(v, k, jnp.inf, left=True))
        k *= 2
    freed_ref[...] = v


ROW_TILE = 8  # batch rows per grid program: the f32 sublane tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def freed_matrix(ends, cores, running, *, interpret: bool = False):
    """Batched Pallas path for the sorted scan: (B, N) tables → (B, N).

    XLA sorts each row (its sort is the part not worth hand-writing), one
    grid program per ``ROW_TILE`` scenario rows runs the O(n) cumsum +
    tie-aware suffix-min in VMEM, and the result scatters back through
    the inverse permutation. A batch of at least ``ROW_TILE`` rows is
    padded to a multiple of it with rows that hold no running job (end
    +inf, cores 0), sliced off afterwards; a smaller batch, such as the
    single row ``freed_vector`` passes under ``jax.vmap``, is one block
    of its own height. Both block shapes meet Mosaic's rule that a
    block's last two dims divide by (8, 128) or equal the array's. Used
    with ``freed_mode="tpu"`` (or ``"interpret"`` for tests); the
    default path computes the reservation without the vector
    (``reservation``). Bit-identical to ``_freed_sorted`` (and to the
    O(n²) reference on integer cores).
    """
    B, N = ends.shape
    e = jnp.where(running.astype(bool), ends, jnp.inf).astype(jnp.float32)
    c = jnp.where(running.astype(bool), cores, 0.0).astype(jnp.float32)
    order = jnp.argsort(e, axis=1)
    e_s = jnp.take_along_axis(e, order, axis=1)
    c_s = jnp.take_along_axis(c, order, axis=1)
    rows = min(B, ROW_TILE)
    pad = (-B) % rows
    if pad:
        e_s = jnp.concatenate([e_s, jnp.full((pad, N), jnp.inf)])
        c_s = jnp.concatenate([c_s, jnp.zeros((pad, N), jnp.float32)])
    block = pl.BlockSpec((rows, N), lambda b: (b, 0))
    freed_s = pl.pallas_call(
        _freed_sorted_kernel,
        grid=((B + pad) // rows,),
        in_specs=[block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B + pad, N), jnp.float32),
        interpret=interpret,
    )(e_s, c_s)[:B]
    inv = jnp.argsort(order, axis=1)
    return jnp.take_along_axis(freed_s, inv, axis=1)


def freed_vector(ends, cores, running, *, mode: str = "ref"):
    """Dispatch the freed-cores scan.

    ``ref``: the sorted O(n log n) jnp path, the Pallas kernel's
    bit-exact reference (``schedule_pass`` in this mode skips the vector
    and computes the reservation directly). ``ref_n2``: the original
    O(n²) pairwise reference, kept for differential checks.
    ``interpret``/``tpu``: the sorted Pallas kernel, run single-scenario;
    under ``jax.vmap`` the batching rule gives it a grid of B one-row
    (1, N) blocks.
    """
    if mode == "ref":
        return _freed_sorted(ends, cores, running)
    if mode == "ref_n2":
        return _freed_math(ends, cores, running)
    if mode in ("interpret", "tpu"):
        return freed_matrix(ends[None, :], cores[None, :], running[None, :],
                            interpret=(mode == "interpret"))[0]
    raise ValueError(f"unknown freed mode {mode!r} (want one of "
                     f"{FREED_MODES})")


def reservation(ends, cores, running, free, head_cores, freed=None):
    """EASY reservation: (shadow_time, spare_cores_at_shadow) for the head.

    Semantics match ``QueueSim._reservation``: walk running jobs by end
    time until the head fits; no feasible point → (+inf, 0).

    Without ``freed`` both scalars come straight from one key-value sort
    of (end, cores): the cores cumsum at sorted position k is what the
    first k+1 enders release, so the shadow is the smallest finite end
    whose cumsum covers the head (a tie run's last position holds the
    run's full total, so ties need no care), and the spare cores are the
    free cores plus those of every job ending by the shadow, less the
    head's. A precomputed ``freed`` vector (the O(n²) reference or the
    Pallas kernel) is read at the earliest feasible row instead. Both
    are exact on integer core counts, whose sums stay below 2**24.
    """
    e = jnp.where(running, ends, jnp.inf)
    if freed is None:
        c = jnp.where(running, cores, 0.0)
        e_s, c_s = jax.lax.sort((e, c), num_keys=1)
        hit = (free + jnp.cumsum(c_s) >= head_cores) & jnp.isfinite(e_s)
        shadow = jnp.min(jnp.where(hit, e_s, jnp.inf))   # +inf: no hit
        spare = free + jnp.sum(jnp.where(e <= shadow, c, 0.0)) - head_cores
        return shadow, jnp.where(jnp.isfinite(shadow), spare, 0.0)
    ok = running & (free + freed >= head_cores)
    pick = jnp.argmin(jnp.where(ok, e, jnp.inf))
    any_ok = jnp.any(ok)
    shadow = jnp.where(any_ok, e[pick], jnp.inf)
    extra = jnp.where(any_ok, free + freed[pick] - head_cores, 0.0)
    return shadow, extra


# ------------------------------------------------------- scheduling pass
def _start_rows(s: ScenarioState, mask: jax.Array, now) -> ScenarioState:
    started_cores = jnp.sum(jnp.where(mask, s.cores, 0.0))
    free = s.free - started_cores
    return s._replace(
        status=jnp.where(mask, RUNNING, s.status),
        start=jnp.where(mask, now, s.start),
        end=jnp.where(mask, now + s.duration, s.end),
        free=free,
        min_free=jnp.minimum(s.min_free, free),
    )


def schedule_pass(s: ScenarioState, *, bf_passes: int = BF_PASSES,
                  freed_mode: str = "ref") -> ScenarioState:
    """One FCFS + EASY-backfill pass at the current sim time ``s.t``.

    Its device work is named ``xsim.schedule`` for the profiler, and the
    reservation's ``xsim.reserve`` inside it, whatever ``freed_mode``
    computes it."""
    with jax.named_scope("xsim.schedule"):
        now = s.t
        n = s.status.shape[0]

        # 1. maximal FCFS prefix that fits --------------------------------
        elig = eligible_mask(s)
        order, rank = fcfs_order(s, elig)
        sorted_elig = elig[order]
        sorted_cores = jnp.where(sorted_elig, s.cores[order], 0.0)
        csum = jnp.cumsum(sorted_cores)
        fits = sorted_elig & (csum <= s.free)
        # cores > 0 ⇒ csum monotone ⇒ `fits` is automatically a prefix
        start_mask = jnp.zeros(n, bool).at[order].set(fits)
        s = _start_rows(s, start_mask, now)

        # 2. reservation for the head (first eligible job that did not
        # fit) ----------------------------------------------------------
        elig = eligible_mask(s)
        n_elig = jnp.sum(elig)
        head = jnp.argmin(jnp.where(elig, rank, n))   # FCFS-first leftover
        has_head = n_elig > 0
        running = s.status == RUNNING
        with jax.named_scope("xsim.reserve"):
            freed = (None if freed_mode == "ref" else
                     freed_vector(s.end, s.cores, running, mode=freed_mode))
            shadow, extra = reservation(
                s.end, s.cores, running, s.free,
                jnp.where(has_head, s.cores[head], 0.0), freed=freed)

        # 3. bounded backfill loop ---------------------------------------
        def body(_, carry):
            s, extra = carry
            elig = eligible_mask(s)
            cand = (elig & (jnp.arange(n) != head) & (s.cores <= s.free)
                    & ((now + s.duration <= shadow) | (s.cores <= extra)))
            pick = jnp.argmin(jnp.where(cand, rank, n))
            do = jnp.any(cand) & has_head
            pick_mask = (jnp.arange(n) == pick) & do
            # QueueSim decrements the reservation's spare only when the
            # job rode in on it (fits_in_extra), even if it also drains in
            # time
            used_extra = jnp.where(do & (s.cores[pick] <= extra),
                                   s.cores[pick], 0.0)
            return _start_rows(s, pick_mask, now), extra - used_extra

        s, _ = jax.lax.fori_loop(0, bf_passes, body, (s, extra))
        return s
