"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

Device planes (``/device:TPU:<n>``) carry one event per operation run on
the chip, on their ``XLA Ops`` line. Busy time is the union of those
intervals inside the traced window, averaged over the chips that ran
anything; idle share is 1 - busy / window. The host plane carries the
harness's ``jax.profiler.TraceAnnotation`` spans (named ``chipbench.*``);
each idle gap of the first busy chip is labelled with the innermost of
them that covers its middle. The window is the ``chipbench.window`` span
when there is one, else the extent of the device operations.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "chipbench.window"
PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
SKIP_LINES = ("XLA Modules", "Steps")


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over chips with operations
    chips: int
    op_s: dict = field(default_factory=dict)     # name -> s, mean per chip
    sort_s: float = 0.0                 # mean per chip, in sort operations
    n_ops: int = 0
    gaps: list = field(default_factory=list)  # [(label, s)], longest first

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(iv):
    iv = sorted(iv)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def is_sort(name: str, stats: dict) -> bool:
    text = " ".join([name, str(stats.get("long_name", "")),
                     str(stats.get("hlo_category", ""))]).lower()
    return "sort" in text


def latest_xplane(logdir: str) -> str | None:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce(path: str, n_gaps: int = 10, n_ops: int = 10) -> Summary | None:
    """The summary of one trace file; None when no device op ran."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    marks, devices, sorts = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        marks.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or [
                ln for ln in lines if ln.name not in SKIP_LINES]
            evs = []
            for ln in ops:
                for e in ln.events:
                    name = e.name
                    if name not in sorts:   # one look per op name
                        sorts[name] = is_sort(name, dict(e.stats))
                    evs.append((name, e.start_ns,
                                e.start_ns + e.duration_ns))
            if evs:
                devices.append(evs)
    if not devices:
        return None
    win = [m for m in marks if m[0] == WINDOW]
    if win:
        w0, w1 = min(m[1] for m in win), max(m[2] for m in win)
    else:
        w0 = min(e[1] for evs in devices for e in evs)
        w1 = max(e[2] for evs in devices for e in evs)
    busy, sort_s, op_s, count = [], 0.0, {}, 0
    for evs in devices:
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in evs
                   if b > w0 and a < w1]
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in evs:
            d = min(b, w1) - max(a, w0)
            if d <= 0:
                continue
            count += 1
            op_s[name] = op_s.get(name, 0.0) + d
            if sorts[name]:
                sort_s += d
    k = len(devices)
    merged = _union([(max(a, w0), min(b, w1)) for _, a, b in devices[0]
                     if b > w0 and a < w1])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    spans = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a), reverse=True)[:n_gaps]
    marks.sort(key=lambda m: m[2] - m[1])     # innermost first
    gaps = []
    for d, a, b in spans:
        mid = (a + b) / 2
        label = next((m[0] for m in marks if m[1] <= mid <= m[2]
                      and m[0] != WINDOW), "no host annotation")
        gaps.append((label, d * 1e-9))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:n_ops]
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / k * 1e-9, chips=k,
                   op_s={n: s / k * 1e-9 for n, s in top},
                   sort_s=sort_s / k * 1e-9, n_ops=count,
                   gaps=gaps[:n_gaps])
