"""Collectives in a compiled program, read from its HLO text.

A copy of the program's `repro/parallel/hlo.py`, kept with the benchmark
so that the count a cell reports cannot change with the program.

`collective_counts(compiled.as_text())` reports, for each collective op,
how many call sites the program holds and how many times they execute
per call: a site inside a `while` body (the model's layer `lax.scan`)
runs once per trip.  The trip count is the one XLA proved and recorded
in the loop's `known_trip_count`, or else — the TPU compiler drops that
record — the constant bound of a condition `i < N`, which is how a scan
counts (from 0, by 1).  This is how a comm plan's effect shows in
the program the chip runs — an SPD-dropped sync is an all-reduce that
no longer executes — next to the ledger's trace-time byte accounting
(parallel/collectives.py)."""
from __future__ import annotations

import re
from typing import Dict

OPS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
       "collective-permute")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(
    r"=\s*(?P<type>.*?)\s(?P<op>" + "|".join(OPS) + r")(?:-start)?\(")
_DTYPE = re.compile(r"\b(pred|[su](?:4|8|16|32|64)|bf16|f16|f32|f64|"
                    r"f8e\w+)\[")
_BODY = re.compile(r"body=%([\w.\-]+)")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_COND = re.compile(r"condition=%([\w.\-]+)")
_CONST = re.compile(r"%([\w.\-]+)\s*=\s*[su]\d+\[\][^ ]*\s+constant\((\d+)\)")
_LT_ROOT = re.compile(r"ROOT .*compare\((.*?)\).*direction=LT")


def collective_counts(hlo_text: str) -> Dict[str, dict]:
    """{op: {"sites": n, "executed": n, "dtypes": {dtype: n}}} for every
    op in OPS.  `executed` multiplies each site by the trip counts of the
    loops around it (a loop without a proven count counts once, and
    every branch of a conditional counts); `dtypes` splits the executions
    by the element type the op moves (a site moving a tuple of types
    counts under each)."""
    comps: Dict[str, dict] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {"ops": [], "calls": [],
                                                "consts": {}, "bound": None})
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        if cur is None:
            continue
        m = _INSTR.search(line)
        if m:
            cur["ops"].append((m.group("op"),
                               tuple(_DTYPE.findall(m.group("type")))))
        c = _CONST.search(line)
        if c:
            cur["consts"][c.group(1)] = int(c.group(2))
        r = _LT_ROOT.search(line)
        if r:
            for arg in re.findall(r"%([\w.\-]+)", r.group(1)):
                if arg in cur["consts"]:
                    cur["bound"] = cur["consts"][arg]
        b = _BODY.search(line)
        if b:
            t = _TRIPS.search(line)
            cond = _COND.search(line)
            cur["calls"].append((b.group(1), int(t.group(1)) if t else
                                 cond.group(1) if cond else 1))
        for c in _CALLS.findall(line):
            cur["calls"].append((c, 1))
        for br in _BRANCHES.findall(line):
            for c in re.findall(r"%([\w.\-]+)", br):
                cur["calls"].append((c, 1))

    out = {op: {"sites": 0, "executed": 0, "dtypes": {}} for op in OPS}
    for comp in comps.values():
        for op, _ in comp["ops"]:
            out[op]["sites"] += 1

    def walk(name, mult, stack):
        comp = comps.get(name)
        if comp is None or name in stack:
            return
        for op, dts in comp["ops"]:
            out[op]["executed"] += mult
            for dt in set(dts):
                out[op]["dtypes"][dt] = out[op]["dtypes"].get(dt, 0) + mult
        for callee, n in comp["calls"]:
            if isinstance(n, str):            # trips from the condition
                n = (comps.get(n) or {}).get("bound") or 1
            walk(callee, mult * n, stack | {name})

    if entry is not None:
        walk(entry, 1, frozenset())
    for v in out.values():
        v["dtypes"] = dict(sorted(v["dtypes"].items()))
    return out
