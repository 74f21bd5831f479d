"""From the program's own spans and named scopes to per-layer numbers.

The program (`repro.obs`) times its host phases as spans when a
`Recorder` is attached: `scheduler.step` and, inside it, `scheduler.admit`
(one admission), `scheduler.prep` (growth, uploads, `pool.cow`,
`pool.table`, the decode dispatch), `scheduler.wait` (blocked on the
device, then the token copy) and `scheduler.commit` (appends, stops).
With `Tracer(profiler=True)` each span is also a
`jax.profiler.TraceAnnotation` of the same name on the profiler's
`/host:CPU` plane, on the device planes' clock.  The compiled programs
carry the name scope `attn` on every op of the paged attention
(`models/attention.paged_attend`) and `sync.b<block>` on every kept sync.

The readers here take:

    spans     (name, start, end) from the Recorder's in-memory tracer
              (`from_tracer`), on the host's perf_counter clock, the
              clock of the harness's window (`Run.w0`, `Run.w1`)
    ops       (name, start_ns, end_ns) of one chip's "XLA Ops" line, each
              op's name scope read from the compiled module's HLO text
              (`hlo_scopes`): the trace does not carry it

and are plain Python, checked on a small fixture (bench/tests/)."""
from __future__ import annotations

import bisect
import re
import statistics
from typing import Callable, Dict, List, Optional, Tuple

from bench.lib.trace import program_id, short_name

Interval = Tuple[str, float, float]          # (name, start, end)


def module_key(module_event_name: str) -> str:
    """'jit_decode_paged(123)' -> 'decode_paged': the program's engine
    key, which names its module (`Engine._step`)."""
    name = module_event_name
    if program_id(name) is not None:
        name = name[:name.rindex("(")]
    return name[4:] if name.startswith("jit_") else name


def from_tracer(events: List[dict], origin: float) -> List[Interval]:
    """The complete slices of a `repro.obs.Tracer` (its Chrome
    `traceEvents`) as (`<track>.<name>`, start, end) in seconds on the
    tracer's clock (`origin` is `Tracer.origin`), sorted by start."""
    tracks = {e["tid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    out = [(f"{tracks[e['tid']]}.{e['name']}", origin + e["ts"] * 1e-6,
            origin + (e["ts"] + e["dur"]) * 1e-6)
           for e in events if e["ph"] == "X"]
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def _within(spans: List[Interval], name: str, lo: float, hi: float
            ) -> List[Interval]:
    return [s for s in spans if s[0] == name and lo < s[2] <= hi]


def decode_steps(spans: List[Interval], lo: float, hi: float
                 ) -> List[Dict[str, float]]:
    """Per `scheduler.step` that ended in (lo, hi] and ran a decode (it
    holds a `scheduler.wait`): the seconds of each child span, summed
    by name."""
    kids = [s for s in spans if s[0] != "scheduler.step"]
    starts = [s[1] for s in kids]
    out = []
    for _, s0, s1 in _within(spans, "scheduler.step", lo, hi):
        per: Dict[str, float] = {}
        for n, a, b in kids[bisect.bisect_left(starts, s0):
                            bisect.bisect_right(starts, s1)]:
            if b <= s1:
                per[n] = per.get(n, 0.0) + (b - a)
        if "scheduler.wait" in per:
            out.append(per)
    return out


def host_ms(spans: List[Interval], lo: float, hi: float
            ) -> Optional[float]:
    """Median over the decode steps in (lo, hi] of `scheduler.prep` +
    `scheduler.commit`, in ms: the host work the synchronous loop makes
    the device wait for."""
    xs = [p.get("scheduler.prep", 0.0) + p.get("scheduler.commit", 0.0)
          for p in decode_steps(spans, lo, hi)]
    return 1e3 * statistics.median(xs) if xs else None


def admit_ms(spans: List[Interval], lo: float, hi: float
             ) -> Optional[float]:
    """Mean `scheduler.admit` duration over the admissions that ended in
    (lo, hi], in ms: how long one admission holds the batch."""
    xs = [b - a for _, a, b in _within(spans, "scheduler.admit", lo, hi)]
    return 1e3 * sum(xs) / len(xs) if xs else None


def innermost(spans: List[Interval], s: float, e: float,
              skip: Callable[[str], bool] = lambda n: False) -> str:
    """The name of the innermost host span that covers most of [s, e]:
    of the spans that cover more than half of it, the shortest; if none
    does, the one that covers the most; 'host:other' if none touches
    it."""
    best, cover, inner, inner_len = "host:other", 0.0, None, None
    for n, ss, se in spans:
        if skip(n):
            continue
        c = min(e, se) - max(s, ss)
        if c > cover:
            best, cover = n, c
        if c > 0.5 * (e - s) and (inner_len is None
                                  or se - ss < inner_len):
            inner, inner_len = n, se - ss
    return inner if inner is not None else best


def leaves(ops: List[Interval]) -> List[Interval]:
    """The ops that enclose no other op on the line: a `while`,
    `conditional` or `call` that runs a body of ops is left out, so no
    time is counted twice."""
    # ops on one line nest or follow each other: in start order (longest
    # first on a tie) an op is a leaf unless the next one starts in it
    evs = sorted(ops, key=lambda t: (t[1], -t[2]))
    return [op for op, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= op[2]]


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction: its `op_name` metadata} of a compiled module's HLO
    text (`compiled.as_text()`), '' where it has none.  The op_name is
    the op's path of name scopes (`jit(decode_paged)/while/body/.../
    attn/...`).  A TPU trace names each op event by the instruction's
    text without its metadata, so the scope is found here."""
    out: Dict[str, str] = {}
    for m in _INSTR.finditer(hlo_text):
        op = _OP_NAME.search(m.group(2))
        out.setdefault(m.group(1), op.group(1) if op else "")
    return out


def scope_share(ops: List[Interval], modules: List[Interval],
                hlo_texts: List[str], kind: str, scope: str, lo: float,
                hi: float) -> Optional[float]:
    """Of the device time of the module executions of program `kind`
    (`module_key`) that lie in [lo, hi] on one chip, the share (%) spent
    in leaf ops (`leaves`) whose name scope holds `scope`.  Each program
    takes its scopes from the compiled module among `hlo_texts` whose
    instructions hold the most of its op names (one module per table
    width: their instructions are numbered apart)."""
    mods = sorted((m for m in modules if lo <= m[1] and m[2] <= hi
                   and module_key(m[0]) == kind), key=lambda t: t[1])
    total = sum(e - s for _, s, e in mods)
    if total <= 0:
        return None
    starts = [m[1] for m in mods]
    by_prog: Dict[Optional[str], List[Interval]] = {}
    for n, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and e <= mods[j][2]:
            by_prog.setdefault(program_id(mods[j][0]), []).append((n, s, e))
    maps = [hlo_scopes(t) for t in hlo_texts]
    part = 0.0
    for prog_ops in by_prog.values():
        names = {short_name(n) for n, _, _ in prog_ops}
        scopes = max(maps, key=lambda m: len(names & m.keys()), default={})
        part += sum(e - s for n, s, e in leaves(prog_ops)
                    if scope in scopes.get(short_name(n), "").split("/"))
    return 100.0 * part / total
