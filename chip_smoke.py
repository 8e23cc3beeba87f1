#!/usr/bin/env python3
"""Smoke run of the system's two device programs on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded paths, on four chips

One chip, two phases, in one process:

* xsim: the fleet sweep through ``make_grid``/``run_grid`` over
  full-size centers (HPC2N 16,856 cores with a backlog of 140, UPPMAX
  9,720 cores with a backlog of 750; ``shrink=1``), the paper's scales,
  the three paper workflows and policies BigJob, Per-Stage and ASA:
  54 cells × 2 seeds = 108 scenarios of 2,313 job rows (the
  configuration's own count is 4 seeds; the run's time limit cuts it).
  The sweep runs
  with the Pallas reservation kernel (``freed_mode="tpu"``) and with
  the jnp reference (``"ref"``); the two final tables must be
  bit-identical, and every workflow must finish. One scenario per
  (center, policy) is then rerun on the host CPU from the same input
  tables, its TWT and makespan compared, and every leaf of the final
  tables that differs named with its largest difference.
* serve: an ``ASAServer`` with 65,536 tenant slots answers a stream of
  requests from over 10,000 tenants, half of them carrying a stage wait
  observed in the xsim phase. Every future must resolve to a
  ``Decision``; the same stream replayed through
  ``serve.asa.decision_step`` on the host CPU must give the same
  ``lead_s``, except where the replay's two best bins are a proven tie
  (within ``TIE_ULPS`` ULP), which rounding decides.

``--chips 4`` runs only what exists across chips, each beside what it is
compared with: ``events.sharded_sweep`` of the same grid over a
4-device ``scenarios`` mesh against the single-chip sweep, and an
``ASAServer`` with ``n_shards=4`` against the unsharded server; both must
agree bit for bit. It prints each device's peak memory. The grid there
has 1 seed per cell (54 scenarios).

The last line printed is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every check passed. Without a TPU, or outside a
checkout of the repository, the script exits non-zero and prints no
result. Times are host-clock seconds of one unrepeated run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PAPER_SEEDS = 4      # per cell: 54 cells × 4 = 216 scenarios
# Seeds per cell run, by chip count. One chip: at 4 seeds the two sweeps
# alone take about 1,000 s on a v5e, too close to the run's 1,200 s
# limit. Four chips: the single-chip sweep it is compared with runs the
# whole grid on one chip too.
SEEDS = {1: 2, 4: 1}
TIE_ULPS = 4         # top-two log_p gap, in ULP, that counts as a MAP tie
N_SLOTS = 65536      # tenant-table slots
BATCH = 256          # queries per serve step
N_TENANTS = 12288    # distinct tenants in the serve stream
N_REQUESTS = 16384   # requests; a tenant recurs only N_TENANTS apart


class Checks:
    """Collects failed checks; the run fails if any did."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def full_size_config():
    from repro.xsim.grid import XSimConfig

    return XSimConfig(n_warm=512, n_backlog=768, n_arrivals=1024,
                      max_stages=9, t0=3600.0)


def same_bits(a, b) -> bool:
    """Two pytrees of arrays hold identical bytes, leaf by leaf."""
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def leaf_differences(a, b) -> list[str]:
    """One line for each leaf of two equal-structured pytrees whose
    bytes differ: its path, how many elements differ, and the largest
    absolute and relative difference among finite pairs."""
    import jax

    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(a)
    for (path, x), y in zip(flat, jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if x.tobytes() == y.tobytes():
            continue
        line = f"{jax.tree_util.keystr(path)} {x.dtype}{list(x.shape)}: "
        if np.issubdtype(x.dtype, np.floating):
            ne = (x != y) & ~(np.isnan(x) & np.isnan(y))
            fin = ne & np.isfinite(x) & np.isfinite(y)
            xf, yf = x[fin].astype(np.float64), y[fin].astype(np.float64)
            d = np.abs(xf - yf)
            r = d / np.maximum(np.abs(yf), 1e-30)
            line += (f"{int(ne.sum())} differ, largest absolute "
                     f"{float(d.max()) if d.size else 0.0!r}, relative "
                     f"{float(r.max()) if r.size else 0.0!r}")
        else:
            line += f"{int((x != y).sum())} differ"
        out.append(line)
    return out


def timed_run_grid(grid, label: str, **kw):
    """One ``run_grid`` call, its wall time split into XLA compile time
    (JAX's own ``backend_compile_duration`` events, which a persistent
    cache hit shortens to the load) and the rest."""
    import jax

    from repro.xsim.grid import run_grid

    compile_s = [0.0]
    cache_hits = [0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        t0 = time.perf_counter()
        final, m = run_grid(grid, **kw)
        jax.block_until_ready((final, m))
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    print(f"xsim: {label}: {wall!r}s wall = {compile_s[0]!r}s XLA compile "
          f"({cache_hits[0]} programs loaded from the persistent cache) + "
          f"{wall - compile_s[0]!r}s running (single unrepeated run)",
          flush=True)
    return final, m


def full_size_grid(cfg, n_seeds: int, where: str):
    from repro.xsim.grid import make_grid

    grid = make_grid(cfg, shrink=1.0, n_seeds=n_seeds)
    cut = (f" (cut from {PAPER_SEEDS} to fit the run's time limit)"
           if n_seeds < PAPER_SEEDS else "")
    print(f"xsim: {grid.n} scenarios x {cfg.max_jobs} rows {where}, step "
          f"budget {cfg.n_steps}, seeds per cell {n_seeds}{cut}",
          flush=True)
    return grid


# ------------------------------------------------------------------ xsim
def xsim_phase(cfg, n_seeds: int, check: Checks):
    """Full-size sweep in both reservation modes, plus the CPU rerun.
    Returns the observed stage waits for the serve phase."""
    import jax

    from repro.xsim import compare, events
    from repro.xsim.grid import initial_states, stage_waits
    from repro.xsim.state import ASA_NAIVE, INVALID, RL

    grid = full_size_grid(cfg, n_seeds, "on one chip")
    final, m = timed_run_grid(grid, "freed_mode=tpu", freed_mode="tpu")
    final_ref, _ = timed_run_grid(grid, "freed_mode=ref", freed_mode="ref")

    check(same_bits(final, final_ref),
          "final tables of freed_mode=tpu and ref are bit-identical")
    m = {k: np.asarray(v) for k, v in m.items()}
    check(bool(np.all(m["wf_done"] == m["wf_total"])),
          f"every workflow finished ({int(m['wf_done'].sum())}/"
          f"{int(m['wf_total'].sum())} stages)")
    nxt = np.asarray(jax.vmap(events.next_event_time)(final))
    check(bool(np.all(np.isposinf(nxt))),
          f"every scenario drained ({int(np.isposinf(nxt).sum())}/"
          f"{grid.n})")

    steps = np.asarray(final.steps)
    print(f"xsim: steps executed max {int(steps.max())} mean "
          f"{float(steps.mean())!r} of budget {cfg.n_steps}", flush=True)
    check(int(steps.max()) < cfg.n_steps, "no scenario hit the step budget")

    states = initial_states(grid)   # what run_grid swept
    fill = 1.0 - np.asarray(states.free) / np.asarray(states.total)
    lo = cfg.n_warm + cfg.n_backlog
    arr_used = (np.asarray(states.status[:, lo:lo + cfg.n_arrivals])
                != INVALID).sum(axis=1)
    centers = np.array([lab["center"] for lab in grid.labels])
    for c in dict.fromkeys(centers):
        sel = centers == c
        print(f"xsim: {c}: {float(np.asarray(states.total)[sel][0]):.0f} "
              f"cores, warm fill {float(fill[sel].min())!r}-"
              f"{float(fill[sel].max())!r} of cores (target "
              f"{cfg.warm_fill}), arrival slots used "
              f"{int(arr_used[sel].min())}-{int(arr_used[sel].max())} of "
              f"{cfg.n_arrivals}", flush=True)

    # one scenario per (center, policy), rerun on the host CPU
    pick: dict[tuple[str, str], int] = {}
    for i, lab in enumerate(grid.labels):
        pick.setdefault((lab["center"], lab["strategy"]), i)
    idx = np.asarray(list(pick.values()))
    cpu = jax.devices("cpu")[0]
    sub = jax.tree.map(lambda x: jax.device_put(np.asarray(x)[idx], cpu),
                       states)
    pols = set(np.asarray(grid.policies).tolist())
    final_cpu = events.sweep(
        sub, n_steps=cfg.n_steps, chunk_steps=cfg.chunk_steps,
        freed_mode="ref", pred_mode=cfg.pred_mode,
        naive=bool(pols & {ASA_NAIVE, RL}), faults=False)
    m_cpu = {k: np.asarray(v)
             for k, v in compare.batched_metrics(final_cpu).items()}
    worst = 0.0
    for j, i in enumerate(idx):
        for k in ("twt_s", "makespan_s"):
            a, b = float(m[k][i]), float(m_cpu[k][j])
            rel = abs(a - b) / max(abs(b), 1.0)
            worst = max(worst, rel)
            if rel > 0.0:
                print(f"xsim: chip vs cpu {grid.labels[i]} {k}: chip {a!r} "
                      f"cpu {b!r}", flush=True)
    diffs = leaf_differences(
        jax.tree.map(lambda x: np.asarray(x)[idx], final), final_cpu)
    tables = f"differ in {len(diffs)} leaves" if diffs else "bit-identical"
    print(f"xsim: chip vs cpu over {len(idx)} scenarios "
          f"({', '.join(f'{c}/{p}' for c, p in pick)}): largest relative "
          f"difference in twt_s/makespan_s {worst!r}; final tables {tables}",
          flush=True)
    for line in diffs:
        print(f"xsim: chip vs cpu leaf {line}", flush=True)
    # f32 rounding of a sum of nine stage waits of up to ~1e6 s
    check(worst <= 1e-5, "chip and cpu agree on twt_s and makespan_s "
          "to f32 rounding")
    waits, valid = stage_waits(final, cfg)
    return waits[valid]


# ----------------------------------------------------------------- serve
def serve_stream(waits: np.ndarray, seed: int = 0):
    """(tenant, observed_wait or None) in submission order: tenants recur
    only N_TENANTS requests apart, so no batch holds one tenant twice
    and the answers do not depend on where the batches were cut."""
    rng = np.random.default_rng(seed)
    tenants = rng.choice(2**31 - 1, size=N_TENANTS, replace=False)
    stream = []
    for i in range(N_REQUESTS):
        w = (float(rng.choice(waits)) if rng.random() < 0.5 else None)
        stream.append((int(tenants[i % N_TENANTS]), w))
    return stream


def serve_run(cfg, stream, check: Checks, label: str):
    """Run ``stream`` through a started server; returns the decisions as
    (lead, expected, entropy) arrays in stream order."""
    from repro.serve.loop import ASAServer, Decision

    srv = ASAServer(cfg)
    srv.start()
    try:
        t0 = time.perf_counter()
        futs = [srv.submit(t, w) for t, w in stream]
        out = []
        for f in futs:
            try:
                out.append(f.result(timeout=600))
            except Exception as e:  # a contained step error fails the run
                out.append(e)
        wall = time.perf_counter() - t0
    finally:
        srv.stop()
    bad = [o for o in out if not isinstance(o, Decision)]
    check(not bad, f"{label}: {len(out) - len(bad)}/{len(out)} futures "
          f"resolved to a Decision"
          + (f" (first failure: {bad[0]!r})" if bad else ""))
    stats = srv.stats
    print(f"serve/{label}: {len(stream)} requests, {srv.n_tenants} tenants, "
          f"{stats['batches']} batches, {stats['deferrals']} deferrals, "
          f"{wall!r}s wall "
          "(single unrepeated run, compile included)", flush=True)
    if bad:
        return None
    return (np.array([o.lead_s for o in out], np.float32),
            np.array([o.expected_s for o in out], np.float32),
            np.array([o.entropy for o in out], np.float32))


def cpu_replay(stream):
    """The same ordered stream through ``decision_step`` with the table
    committed to the host CPU: slots in order of first appearance.
    Returns (lead, expected, entropy, gap, top): the decisions, and the
    first and second largest log_p of the posterior each was read from
    as their difference and the largest."""
    import jax

    from repro.parallel import fleet as pfleet
    from repro.serve import asa as serve_asa

    cpu = jax.devices("cpu")[0]
    table = jax.device_put(serve_asa.init_table(N_SLOTS), cpu)
    slot_of: dict[int, int] = {}
    outs = []
    for lo in range(0, len(stream), BATCH):
        chunk = stream[lo:lo + BATCH]
        q = serve_asa.QueryBatch(
            slot=np.array([slot_of.setdefault(t, len(slot_of))
                           for t, _ in chunk], np.int32),
            observed_wait=np.array([w or 0.0 for _, w in chunk], np.float32),
            has_obs=np.array([w is not None for _, w in chunk]))
        q = jax.device_put(q, cpu)
        qp, mask = pfleet.pad_batch(q, BATCH)
        table, dec = serve_asa.decision_step(table, qp, mask)
        log_p = np.sort(np.asarray(table.log_p[q.slot]), axis=1)
        outs.append([np.asarray(x)[:len(chunk)] for x in dec]
                    + [log_p[:, -1] - log_p[:, -2], log_p[:, -1]])
    return tuple(np.concatenate(col) for col in zip(*outs))


def serve_phase(waits: np.ndarray, check: Checks):
    from repro.serve.loop import ServeConfig

    stream = serve_stream(waits)
    n_obs = sum(w is not None for _, w in stream)
    print(f"serve: {N_SLOTS} slots, batch {BATCH}; {len(stream)} requests "
          f"from {N_TENANTS} tenants, {n_obs} carrying an observed xsim "
          "stage wait", flush=True)
    chip = serve_run(ServeConfig(n_slots=N_SLOTS, batch_size=BATCH), stream,
                     check, "chip")
    if chip is None:
        return
    ref = cpu_replay(stream)
    gap, top = ref[3], ref[4]
    # two bins with the same loss history are equal but for the order
    # of their f32 updates and the backend's exp/log: an ULP or two
    tol = TIE_ULPS * np.spacing(np.abs(top))
    tied = gap <= tol
    differ = chip[0] != ref[0]
    print(f"serve: {int(tied.sum())} of {len(gap)} decisions read a "
          f"posterior whose two best bins are within {TIE_ULPS} ULP (a "
          f"tie), {int(((gap > tol) & (gap < 1e-3)).sum())} within "
          "1e-3 nat but further apart", flush=True)
    check(not np.any(differ & ~tied),
          f"lead_s equals the CPU replay except on ties ({int(differ.sum())}"
          f" differ, {int((differ & tied).sum())} of them on ties)")
    for name, a, b in (("expected_s", chip[1], ref[1]),
                       ("entropy", chip[2], ref[2])):
        rel = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
        print(f"serve: chip vs cpu {name}: largest relative difference "
              f"{float(rel.max())!r}, {int((a != b).sum())} of {len(a)} "
              "differ", flush=True)


# ------------------------------------------------------------- four chips
def peak_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"memory: {d} peak_bytes_in_use "
              f"{peak if peak is not None else 'not reported'}", flush=True)


def sharded_phase(cfg, n_seeds: int, n_chips: int, check: Checks) -> None:
    import jax

    from repro.launch.mesh import make_scenarios_mesh
    from repro.serve.loop import ServeConfig
    from repro.xsim.grid import stage_waits

    grid = full_size_grid(cfg, n_seeds, f"over {n_chips} chips")
    mesh = make_scenarios_mesh(n_chips)
    one, _ = timed_run_grid(grid, "single-chip vmap sweep",
                            freed_mode="tpu")
    many, _ = timed_run_grid(grid, f"{n_chips}-chip sharded sweep",
                             freed_mode="tpu", mesh=mesh)
    check(same_bits(one, many), f"sharded sweep over {n_chips} chips is "
          "bit-identical to the single-chip sweep")
    peak_memory(jax.devices()[:n_chips])

    waits, valid = stage_waits(one, cfg)
    stream = serve_stream(waits[valid])
    base = serve_run(ServeConfig(n_slots=N_SLOTS, batch_size=BATCH), stream,
                     check, "unsharded")
    shard = serve_run(ServeConfig(n_slots=N_SLOTS, batch_size=BATCH,
                                  n_shards=n_chips), stream, check,
                      f"{n_chips}-shard")
    if base is not None and shard is not None:
        check(all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                  for a, b in zip(base, shard)),
              f"{n_chips}-shard server decisions are bit-identical to the "
              "unsharded server's")
    peak_memory(jax.devices()[:n_chips])


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep and server, "
                         "each against its single-chip counterpart")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.runtime import compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {compile_cache.enable()}", flush=True)
    check = Checks()
    cfg = full_size_config()
    t0 = time.perf_counter()
    if args.chips == 1:
        waits = xsim_phase(cfg, SEEDS[1], check)
        serve_phase(waits, check)
    else:
        sharded_phase(cfg, SEEDS[args.chips], args.chips, check)
    print(f"total {time.perf_counter() - t0!r}s", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              + "; ".join(check.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
