"""Observability for the xsim/RL/bench stack.

* ``obs.trace`` — device-resident per-scenario event ring buffers,
  appended inside the jitted event scan (``trace=None`` elides them).
* ``obs.metrics`` — counters/histograms registry with vmap- and
  shard_map-aware fleet reductions.
* ``obs.export`` — host-side decoding to Chrome trace-event JSON /
  JSONL, schema validation.
* ``obs.telemetry`` — the unified (stdlib-only) telemetry schema all
  bench runners emit and ``bench_gate`` consumes.

Deliberately NOT importing submodules here: ``obs.telemetry`` must stay
importable from environments without jax (bench_gate in CI).
"""
