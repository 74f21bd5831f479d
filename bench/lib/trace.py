"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

A TPU trace holds, per chip, a plane `/device:TPU:<i>` with a line
"XLA Modules" (one event per program execution, named
`<module>(<program id>)`) and a line "XLA Ops" (one event per operation,
named by its HLO instruction text).  The host plane `/host:CPU` holds
the benchmark's own `jax.profiler.TraceAnnotation` spans, all named
`bench.*`.  Every step program of the server is a module named
`jit_local`, so a program is told apart by its id: `label_programs`
maps ids to kinds from a short labelled session in which each program
kind runs once inside a span `bench.label.<kind>`.

The reduction is plain Python over (name, start, end) tuples in
nanoseconds, so it can be checked on a small recorded fixture
(bench/tests/fixtures/)."""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

_MODULE = re.compile(r"^(?P<name>.*)\((?P<pid>-?\d+)\)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_.\-]*)\(")


@dataclass
class Trace:
    """Device modules and ops per chip, and the benchmark's host spans."""

    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)

    def chips(self) -> List[str]:
        return sorted(self.modules, key=_chip_index)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda evs: [tuple(e) for e in evs]
        return cls({k: tup(v) for k, v in d["modules"].items()},
                   {k: tup(v) for k, v in d["ops"].items()},
                   tup(d["spans"]))


def _chip_index(plane: str) -> int:
    return int(plane.rsplit(":", 1)[-1])


def load(path: str) -> Trace:
    """Read an .xplane.pb with JAX's own reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                (tr.modules if line.name == "XLA Modules"
                 else tr.ops)[name] = evs
        elif name == "/host:CPU":
            for line in plane.lines:
                tr.spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("bench."))
    tr.spans.sort(key=lambda s: s[1])
    return tr


def find_xplane(directory: str) -> str:
    import glob
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def program_id(module_event_name: str) -> Optional[str]:
    m = _MODULE.match(module_event_name)
    return m.group("pid") if m else None


def opcode(op_event_name: str) -> str:
    """'%fusion.3 = bf16[..] fusion(...), ...' -> 'fusion'."""
    rhs = op_event_name.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else rhs.split("(", 1)[0].strip()


def short_name(op_event_name: str) -> str:
    """'%fusion.3 = ...' -> 'fusion.3'."""
    return op_event_name.split(" = ", 1)[0].lstrip("%").strip()


def label_programs(tr: Trace, tol_ns: float = 2e6) -> Dict[str, str]:
    """{program id: kind} from spans `bench.label.<kind>`: a module
    execution on any chip takes the kind of the label span nearest its
    midpoint (inside it, or within `tol_ns`, which covers the offset
    between the host's and the device's clocks)."""
    labels = [(n[len("bench.label."):], s, e) for n, s, e in tr.spans
              if n.startswith("bench.label.")]
    out: Dict[str, str] = {}
    for evs in tr.modules.values():
        for name, s, e in evs:
            mid = (s + e) / 2
            dist = [(max(ls - mid, mid - le, 0.0), kind)
                    for kind, ls, le in labels]
            pid = program_id(name)
            if dist and pid is not None:
                d, kind = min(dist)
                if d <= tol_ns:
                    out[pid] = kind
    return out


def _clip(evs: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def merged(evs: List[Interval]) -> List[Tuple[float, float]]:
    """Union of the intervals, as sorted disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(evs, key=lambda t: t[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _span_at(spans: List[Interval], s: float, e: float) -> str:
    """The host span that covers most of [s, e], or 'host:other'."""
    best, cover = "host:other", 0.0
    for n, ss, se in spans:
        if n.startswith("bench.label.") or n == "bench.traced":
            continue
        c = min(e, se) - max(s, ss)
        if c > cover:
            best, cover = n, c
    return best


def reduce(tr: Trace, labels: Dict[str, str], lo: float, hi: float,
           top: int = 10) -> dict:
    """The traced window [lo, hi] (ns) reduced to:

    window_s           hi - lo
    busy_s             per chip, the union of its op intervals
    programs           per kind, on the first chip: device seconds (sum
                       of its module executions) and calls
    allreduce_s        per kind, on the first chip: seconds of all-reduce
                       ops inside that kind's module executions
    device_ops         the `top` op names (kind:instruction) by seconds
                       on the first chip
    idle_gaps          the `top` longest gaps between ops on the first
                       chip, each named by the host span it falls in
    """
    chips = tr.chips()
    out = {"window_s": (hi - lo) / 1e9, "busy_s": {}, "programs": {},
           "allreduce_s": {}, "device_ops": [], "idle_gaps": []}
    for c in chips:
        out["busy_s"][c] = sum(e - s for s, e in
                               merged(_clip(tr.ops.get(c, []), lo, hi))) / 1e9
    if not chips:
        return out
    c0 = chips[0]
    mods = sorted(_clip(tr.modules.get(c0, []), lo, hi), key=lambda t: t[1])
    kinds = [labels.get(program_id(n), "other") for n, _, _ in mods]
    prog = defaultdict(lambda: [0.0, 0])
    for (n, s, e), k in zip(mods, kinds):
        prog[k][0] += (e - s) / 1e9
        prog[k][1] += 1
    out["programs"] = {k: {"device_s": v[0], "calls": v[1]}
                       for k, v in prog.items()}
    # attribute each op to the module execution that contains it
    ops = sorted(_clip(tr.ops.get(c0, []), lo, hi), key=lambda t: t[1])
    per_op = defaultdict(float)
    ar = defaultdict(float)
    j = 0
    for name, s, e in ops:
        while j < len(mods) and mods[j][2] < s:
            j += 1
        k = kinds[j] if j < len(mods) and mods[j][1] <= s else "other"
        per_op[f"{k}:{short_name(name)}"] += (e - s) / 1e9
        if "all-reduce" in opcode(name):
            ar[k] += (e - s) / 1e9
    out["allreduce_s"] = dict(ar)
    out["device_ops"] = [[n, v] for n, v in sorted(
        per_op.items(), key=lambda t: -t[1])[:top]]
    busy = merged(ops)
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out["idle_gaps"] = [[_span_at(tr.spans, s, e), (e - s) / 1e9]
                        for s, e in gaps[:top]]
    return out
