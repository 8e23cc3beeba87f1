"""The program's own names in a ``jax.profiler`` trace: device time per
named scope, and the serve loop's phases.

The program names its device work with ``jax.named_scope`` (``SCOPES``);
the names reach each compiled operation's metadata (``op_name``, a path
such as ``jit(sweep)/while/body/xsim.schedule/xsim.reserve/sort``). The
trace's ``/host:metadata`` plane stores every compiled module's HLO,
one event metadata per module (id = program id, name ``jit_f(id)``),
from which each operation's path is read; a device op event (the
``XLA Ops`` line, named by its HLO text ``%fusion.625 = ...``) finds
its module by the ``XLA Modules`` event around it (``jit_f(id)``).
Each op's *self* time (its interval less the op events nested in it,
so a ``while`` counts only its own loop control) goes to the innermost
of ``SCOPES`` on its path; JAX's own path parts (``jit(...)``,
``while``, ``body``) do not count. Times are clipped to the harness's
``chipbench.window`` span, summed per chip and averaged over the chips
that ran anything, as ``trace_reduce`` does.

The serve loop writes ``asa.serve.<phase>`` annotations on its own
thread (``repro.obs.serve_obs.ServeObs.phase``); their intervals are
returned as they are, for the readers to clip.

A program that names nothing (an older one) leaves every scope empty
and has no ``asa.serve.*`` annotation: the readers then return None.

    python3 chipbench/scopes.py [trace.xplane.pb]   # a summary, as JSON
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
import traceback
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace_reduce  # noqa: E402

SCOPES = ("xsim.events", "xsim.schedule", "xsim.reserve", "xsim.hooks",
          "asa.update", "asa.read")
SERVE_PREFIX = "asa.serve."
HLO_PROTO = b"Hlo Proto"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\((\d+)\)\s*$")
_ROOT = Path(__file__).resolve().parent.parent


@dataclass
class ScopeSummary:
    window: tuple                       # (start_ns, end_ns)
    chips: int
    busy_s: float                       # self time of every op, per chip
    scope_s: dict = field(default_factory=dict)   # scope -> s, per chip
    unscoped_s: float = 0.0
    program_s: dict = field(default_factory=dict)  # module -> s, per chip
    top_ops: list = field(default_factory=list)  # [(op, scope, module, s)]
    serve: dict = field(default_factory=dict)  # phase -> [(a_ns, b_ns)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def scoped(self) -> bool:
        return any(v > 0 for v in self.scope_s.values())

    def frac(self, *scopes: str) -> float | None:
        """Share of the busy time under any of ``scopes``; None when the
        program named nothing."""
        if not self.scoped or self.busy_s <= 0:
            return None
        return sum(self.scope_s.get(s, 0.0) for s in scopes) / self.busy_s

    def serve_in_window(self, phase: str) -> list:
        """The phase's intervals clipped to the window (ns)."""
        w0, w1 = self.window
        return [(max(a, w0), min(b, w1)) for a, b in self.serve.get(phase, ())
                if b > w0 and a < w1]

    def batches(self) -> int:
        """Batches dispatched in the window: ``device_step`` phases that
        start inside it."""
        w0, w1 = self.window
        return sum(1 for a, _b in self.serve.get("device_step", ())
                   if w0 <= a <= w1)


# --------------------------------------------------- protobuf wire format
def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} not expected")
        yield num, v


def innermost_scope(path: str) -> str | None:
    """The last of ``SCOPES`` among the parts of an ``op_name`` path; a
    part wrapped by a transformation (``vmap(xsim.events)``) counts."""
    for part in reversed(path.split("/")):
        name = part.rstrip(")").rsplit("(", 1)[-1]
        if name in SCOPES:
            return name
    return None


def module_scopes(path: str) -> dict:
    """program id -> {HLO instruction name: innermost scope or None}, for
    every module the trace's ``/host:metadata`` plane stores. XSpace:
    planes 1; XPlane: name 2, event_metadata 4 (map entry: value 2),
    stat_metadata 5; XEventMetadata: id 1, stats 5; XStat: metadata_id
    1, bytes 6; HloProto: module 1; module: computations 3; computation:
    instructions 2; instruction: name 1, metadata 7; OpMetadata: op_name
    2."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for num, plane in _fields(data):
        if num != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        parts = list(_fields(plane))
        stat_ids = {_first(_first(v, 2), 1) for n, v in parts
                    if n == 5 and _first(_first(v, 2), 2) == HLO_PROTO}
        for n, v in parts:
            if n != 4:
                continue
            pid, proto = None, None
            for mnum, mv in _fields(_first(v, 2)):
                if mnum == 1:
                    pid = mv
                elif mnum == 5 and _first(mv, 1, 0) in stat_ids:
                    proto = _first(mv, 6)
            if pid is not None and proto is not None:
                out[pid] = _instruction_scopes(proto)
    return out


def _first(msg, num: int, default=b""):
    """The first value of field ``num`` in ``msg``."""
    for n, v in _fields(msg):
        if n == num:
            return v
    return default


def _instruction_scopes(proto) -> dict:
    names = {}
    for num, comp in _fields(_first(proto, 1)):
        if num != 3:
            continue
        for cnum, ins in _fields(comp):
            if cnum != 2:
                continue
            name, op_name = None, ""
            for inum, iv in _fields(ins):
                if inum == 1:
                    name = bytes(iv).decode()
                elif inum == 7:
                    op_name = bytes(_first(iv, 2)).decode()
            if name is not None:
                names[name] = innermost_scope(op_name)
    return names


# ---------------------------------------------------------- attribution
def self_times(events) -> dict:
    """key -> self time of ``[(start, end, key)]``: at every instant the
    innermost (latest started) open event takes the time, so the values
    sum to the length of the union of the intervals when they nest."""
    out: dict = {}
    stack: list = []          # [end, key], innermost last
    cur = 0
    for a, b, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, k = stack.pop()
            out[k] = out.get(k, 0) + end - cur
            cur = end
        if stack:
            top = stack[-1]
            out[top[1]] = out.get(top[1], 0) + a - cur
            b = min(b, top[0])
        cur = a
        if b > a:
            stack.append([b, key])
        else:
            out.setdefault(key, 0)
    while stack:
        end, k = stack.pop()
        out[k] = out.get(k, 0) + end - cur
        cur = end
    return out


def _op_name(name: str) -> str:
    """``%fusion.625 = s32[...] ...`` or ``fusion.625`` -> ``fusion.625``."""
    return name.split(" ", 1)[0].lstrip("%")


def summarize(path: str, n_top: int = 10) -> ScopeSummary | None:
    """The scope summary of one trace file; None when no device op ran."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, serve, devices = None, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n == trace_reduce.WINDOW:
                        iv = (e.start_ns, e.start_ns + e.duration_ns)
                        window = iv if window is None else (
                            min(window[0], iv[0]), max(window[1], iv[1]))
                    elif n.startswith(SERVE_PREFIX):
                        serve.setdefault(n[len(SERVE_PREFIX):], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
        elif (plane.name.startswith("/device:")
              and "CPU" not in plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == trace_reduce.OPS_LINE] \
                or [ln for ln in lines
                    if ln.name not in trace_reduce.SKIP_LINES]
            modules = []
            for ln in lines:
                if ln.name == MODULES_LINE:
                    for e in ln.events:
                        m = _MODULE_ID.search(e.name)
                        if m:
                            modules.append((e.start_ns,
                                            e.start_ns + e.duration_ns,
                                            int(m.group(1)),
                                            e.name[:m.start()]))
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for ln in ops for e in ln.events]
            if evs:
                devices.append((evs, sorted(modules)))
    if not devices:
        return None
    if window is None:
        window = (min(e[0] for evs, _ in devices for e in evs),
                  max(e[1] for evs, _ in devices for e in evs))
    w0, w1 = window
    hlo = module_scopes(path)
    key_scope: dict = {}
    op_ns: dict = {}
    for evs, modules in devices:
        clipped = []
        for a, b, name in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            pid, module = _module_at(a, modules)
            key = (module, _op_name(name))
            if key not in key_scope:
                key_scope[key] = hlo.get(pid, {}).get(key[1])
            clipped.append((a, b, key))
        for key, ns in self_times(clipped).items():
            op_ns[key] = op_ns.get(key, 0) + ns
    k = len(devices)
    scope_s, program_s = {}, {}
    for key, ns in op_ns.items():
        sc = key_scope[key]
        scope_s[sc] = scope_s.get(sc, 0.0) + ns / k * 1e-9
        program_s[key[0]] = program_s.get(key[0], 0.0) + ns / k * 1e-9
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:n_top]
    return ScopeSummary(
        window=window, chips=k, busy_s=sum(scope_s.values()),
        scope_s={sc: scope_s.get(sc, 0.0) for sc in SCOPES},
        unscoped_s=scope_s.get(None, 0.0), program_s=program_s,
        top_ops=[(op, key_scope[(mod, op)], mod, ns / k * 1e-9)
                 for (mod, op), ns in top],
        serve=serve)


def _module_at(t: int, modules):
    """(program id, module name) of the ``XLA Modules`` event running at
    ``t``; (None, None) outside every module."""
    k = bisect.bisect_right(modules, (t, float("inf")))
    if k and modules[k - 1][1] >= t:
        return modules[k - 1][2:]
    return None, None


def covered_s(intervals) -> float:
    """Seconds covered by the union of ``[(start_ns, end_ns)]``."""
    return sum(b - a for a, b in trace_reduce._union(intervals)) * 1e-9


_CACHE: dict = {}


def read(root: Path = _ROOT) -> ScopeSummary | None:
    """The summary of the run's trace under ``<root>/.chipbench/trace``,
    parsed once per run (cached on the file and its mtime). A trace this
    module cannot read gives None and a note on standard error, so the
    metrics that read it are left out rather than failing the run."""
    path = trace_reduce.latest_xplane(str(root / ".chipbench" / "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        try:
            _CACHE[key] = summarize(path)
        except Exception:  # noqa: BLE001 (a reader never fails a run)
            print(f"chipbench.scopes: cannot read {path}", file=sys.stderr)
            traceback.print_exc()
            _CACHE[key] = None
    return _CACHE[key]


def of_run(ctx: dict, cell_key: str) -> ScopeSummary | None:
    """The summary of a traced run of a cell whose code puts ``cell_key``
    in the readers' context; None otherwise."""
    if cell_key not in ctx or ctx.get("trace_span") is None:
        return None
    return read()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else trace_reduce.latest_xplane(
        str(_ROOT / ".chipbench" / "trace"))
    s = summarize(path) if path else None
    if s is None:
        print("no device operation in the trace", file=sys.stderr)
        return 1
    print(json.dumps({
        "window_s": s.window_s, "chips": s.chips, "busy_s": s.busy_s,
        "scope_s": s.scope_s, "unscoped_s": s.unscoped_s,
        "program_s": s.program_s, "top_ops": s.top_ops,
        "batches": s.batches(),
        "serve_s": {p: [len(s.serve_in_window(p)),
                        covered_s(s.serve_in_window(p))] for p in s.serve}},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
