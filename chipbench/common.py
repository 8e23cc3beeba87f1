"""Pieces every driver shares: seeds, devices, statistics, the result line."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of a run's ``--seed``."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def sub_seed(seed: int, *stream: int) -> int:
    """A 31-bit seed for a program API that takes an int32."""
    return int(rng(seed, *stream).integers(0, 2**31 - 1))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that
    is not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS.read_text())["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation; +inf entries
    (requests that never resolved) sort last."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class CompileWatch:
    """Counts XLA compilations (cache loads included) while it is open, so
    a run can show that nothing compiled inside its window."""

    def __init__(self) -> None:
        self.n = 0
        self.seconds = 0.0

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        print(f"compiles in the window: {self.n} ({self.seconds:.3f}s)",
              file=sys.stderr, flush=True)


class Checks:
    """The numbers compared against the reference, each with its limit."""

    def __init__(self) -> None:
        self.items: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            np.isfinite(v) and v <= lim for _, v, lim in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def print_stderr(self) -> None:
        for n, v, lim in self.items:
            verdict = "ok" if np.isfinite(v) and v <= lim else "FAIL"
            print(f"check {n}: {v!r} (limit {lim!r}) {verdict}",
                  file=sys.stderr, flush=True)


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: Checks, breakdown: dict | None = None) -> None:
    """The result: the last line of standard output."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.as_dict()
    checks.print_stderr()
    print(json.dumps(out), flush=True)
