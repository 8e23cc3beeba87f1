#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload uppmax.sweep --seed 7 \\
        --seconds 40 --trace 0

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``: the configuration's file, ``chipbench/traffic/
<traffic>.json`` (whose ``kind`` picks the driver in
``chipbench/drivers/``) and, with ``--trace 1``, one reader per
per-layer metric in ``chipbench/metrics/<name>.py``. A run loads, warms
up, measures for ``--seconds``, checks what the measured calls produced
against the plain reference, and prints one JSON object as the last
line of standard output. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class Tracer:
    """The profiler session of a ``--trace 1`` run, and the harness's
    host annotations (no-ops when tracing is off)."""

    def __init__(self, enabled: bool, logdir: Path) -> None:
        self.enabled = enabled
        self.logdir = logdir
        self.span = None
        self._window = None

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> bool:
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        self.logdir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.logdir))
        self._window = jax.profiler.TraceAnnotation("chipbench.window")
        self._window.__enter__()
        self.span = (time.perf_counter(), None)
        return True

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        self.span = (self.span[0], time.perf_counter())
        jax.profiler.stop_trace()

    def reduce(self):
        """The trace's summary (None when nothing ran on a device)."""
        from chipbench import trace_reduce

        if self.span is None:
            return None
        path = trace_reduce.latest_xplane(str(self.logdir))
        return trace_reduce.reduce(path) if path else None


def load_cell(root: Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
        .read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    layer = [m for m in bench["per_layer"] if applies(m)]
    return cell, cfg, traffic, e2e, layer


def read_layer_metric(root: Path, name: str, ctx: dict):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, cfg, traffic, e2e_defs, layer_defs = load_cell(root,
                                                         args.workload)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: with it every write scans the whole directory, and a
    # cell that caches thousands of small programs stalls its set-up
    jax.config.update("jax_compilation_cache_max_size", -1)

    from chipbench import common

    driver = importlib.import_module(f"chipbench.drivers.{traffic['kind']}")
    tracer = Tracer(bool(args.trace), root / ".chipbench" / "trace")
    out = driver.run(cell, cfg, traffic, args.seed, args.seconds,
                     args.trace, devices, T_START, tracer)
    device = common.device_info(devices)

    def peak_read():
        device["memory_peak_bytes"] = common.memory_peak(devices)

    checks = out["check"](peak_read)
    units = {m["name"]: m["unit"] for m in e2e_defs + layer_defs}
    metrics, breakdown = {}, None
    if args.trace:
        ctx = dict(out["ctx"], trace=tracer.reduce(), trace_span=tracer.span)
        for m in layer_defs:
            v = read_layer_metric(root, m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[
                    m["name"]]}
        s = ctx.get("trace")
        if s is not None:
            device["busy_s"] = s.busy_s
            device["window_s"] = s.window_s
            breakdown = {"device_ops": [[n, v] for n, v in s.op_s.items()],
                         "idle_gaps": [[n, v] for n, v in s.gaps]}
    else:
        vals = dict(out["e2e"], setup_s=out["setup_s"])
        for m in e2e_defs:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                      "unit": units[m["name"]]}
    common.emit(checks.ok and out["failed"] == 0, out["attempted"],
                out["failed"], metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
