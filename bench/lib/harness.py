"""One run of one cell: set-up, the measured window, and the records the
metric readers take their numbers from.

The entry the window drives is the program's own serving loop:
`LLM.load(...)`, then `LLM.serve()`, requests put on
`Scheduler.submit` and the loop turned by `Scheduler.step()`.  The
benchmark's host spans (`bench.step`, `bench.submit`, `bench.wait`) are
`jax.profiler.TraceAnnotation`s around those calls, so a traced run can
say what the host was doing in each idle gap of the device."""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench.lib import traffic as T
from bench.lib.decoder import Arch


class CompileCounter:
    """Compile seconds, compile-cache hits and misses, and the times of
    backend compilations, from JAX's monitoring events (the arithmetic of
    the program's chip_smoke.py `Phase`)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.compile_s = 0.0
        self.backend_compiles: List[float] = []

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **kw):
            if event in self.EVENTS:
                self.compile_s += duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend_compiles.append(time.perf_counter())

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "backend_compiles": len(self.backend_compiles)}


@dataclass
class Step:
    """One `Scheduler.step()` as the harness saw it."""

    t0: float
    t1: float
    rows: int                  # requests that got a decode token
    ctx_sum: int               # their context lengths, summed
    prefills: List[int]        # prompt tokens of each admission
    pool_used: int
    queue: int


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: object
    arch: Arch
    tp: int
    max_batch: int
    num_pages: int
    seconds: float
    peaks: dict
    chips: int
    requests: List[T.Request] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    t_nominal: float = 0.0     # nominal window start; due times count from it
    w0: float = 0.0            # window: first step end at/after t_nominal ...
    w1: float = 0.0            # ... to the first step end at/after + seconds
    setup_s: float = 0.0
    compiles_in_window: int = 0
    lateness: List[float] = field(default_factory=list)
    trace: Optional[dict] = None        # trace.reduce() of the traced part
    traced: Optional[tuple] = None      # (t0, t1) host times of that part
    allreduces_per_decode: Optional[int] = None

    # -- helpers the readers share --
    def window_steps(self, lo=None, hi=None) -> List[Step]:
        lo = self.w0 if lo is None else lo
        hi = self.w1 if hi is None else hi
        return [s for s in self.steps if lo < s.t1 <= hi]

    def traced_steps(self) -> List[Step]:
        return self.window_steps(*self.traced) if self.traced else []

    def due_in_window(self) -> List[T.Request]:
        """Requests due in the window: open loop by schedule, closed loop
        by when their client sent them."""
        lo, hi = self.t_nominal, self.t_nominal + self.seconds
        return [r for r in self.requests
                if r.sent is not None and lo <= self._due(r) < hi]

    def _due(self, r: T.Request) -> float:
        return r.sent if r.due is None else self.t_nominal + r.due

    def ttfts(self) -> List[float]:
        """Seconds from due to first token, for every request due in the
        window; one still waiting at the window's end enters at its wait
        so far."""
        out = []
        for r in self.due_in_window():
            first = r.first if r.first is not None and r.first <= self.w1 \
                else self.w1
            out.append(first - self._due(r))
        return out

    def token_gaps(self) -> List[float]:
        """Every gap between consecutive output tokens of a request whose
        later token came in the window."""
        out = []
        for r in self.requests:
            tt = r.token_times
            for a, b in zip(tt, tt[1:]):
                if self.w0 < b <= self.w1:
                    out.append(b - a)
        return out

    def window_tokens(self) -> int:
        return sum(1 for r in self.requests for t in r.token_times
                   if self.w0 < t <= self.w1)

    def queue_waits(self) -> List[float]:
        out = []
        for r in self.due_in_window():
            adm = r.admitted
            end = adm if adm is not None and adm <= self.w1 else self.w1
            out.append(end - self._due(r))
        return out


def percentile(xs: List[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation; None if empty."""
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def table_buckets(mix: dict, page_size: int, pages_per_slot: int,
                  max_positions: int) -> List[int]:
    """The page-table widths the scheduler can reach under this mix: it
    buckets the widest slot's owned pages to a power of two, from a lone
    request's admission (shortest prompt + 1) to the longest request."""
    def pow2(n):
        return 1 << max(0, math.ceil(math.log2(max(n, 1))))

    lo_tokens = mix["prompt_len"]["min"] + 1
    hi_tokens = min(mix["prompt_len"]["max"] + mix["output_len"]["max"],
                    max_positions)
    lo = min(pow2(-(-lo_tokens // page_size)), pages_per_slot)
    hi = min(pow2(-(-hi_tokens // page_size)), pages_per_slot)
    out, w = [], lo
    while w <= hi:
        out.append(w)
        w <<= 1
    return out


class Server:
    """The program under test, loaded for one cell, and the calls the
    harness makes into it."""

    def __init__(self, cell, params_host, ModelConfig, LLM):
        cfg = cell.config
        model = cell.model_module()
        load = cfg["load"]
        self.cfg = ModelConfig(name=cell.config_name, dtype=cfg["torch_dtype"],
                               **model.program_config(cfg))
        self.llm = LLM.load(
            self.cfg, engine="shard", tp=load["tp"], spd=load["spd"],
            params=params_host, page_size=load["page_size"],
            num_pages=load["num_pages"],
            prefill_chunk=load["prefill_chunk"],
            cache_len=load["cache_len"], max_batch=load["max_batch"])
        over = {k: cell.traffic[k] for k in ("prefix_cache",)
                if k in cell.traffic}
        self.sched = self.llm.serve(**over) if over else self.llm.serve()
        self.load = load

    # -- warm-up and labelling: the shapes this cell's traffic reaches --
    def decode_once(self, width: int):
        import jax
        import jax.numpy as jnp

        s, kv = self.sched, self.sched.kv
        table = jnp.full((s.max_batch, width), -1, jnp.int32)
        nxt, kv.pcaches = self.llm.engine.decode_paged(
            self.llm.params, jnp.asarray(s.cur), jnp.asarray(s.pos), table,
            kv.pcaches)
        jax.block_until_ready(nxt)

    def prefill_once(self):
        import jax
        import jax.numpy as jnp

        c = self.load["prefill_chunk"]
        lg, caches1 = self.llm.engine.prefill_chunked(
            self.llm.params, jnp.zeros((1, c), jnp.int32),
            cache_len=self.load["cache_len"], lengths=np.asarray([c]),
            chunk=c)
        jax.block_until_ready(lg)
        return caches1

    def insert_once(self, caches1):
        import jax

        kv = self.sched.kv
        row = np.full(kv.pool.pages_per_slot, -1, np.int32)
        kv.pcaches = self.llm.engine.insert_paged(kv.pcaches, caches1, 0,
                                                  row)
        jax.block_until_ready(kv.pcaches)

    def warm(self, buckets: List[int], vocab: int):
        """Compile (or load from the cache) every program the window can
        run: one request through admission (chunked prefill, insertion
        into the pool, first-token sampling) and decode, then the decode
        step at every table width in `buckets`."""
        from repro.api.scheduler import Request

        c = self.load["prefill_chunk"]
        rng = np.random.default_rng(0)
        req = Request(uid=-1, prompt=rng.integers(0, vocab, c + 1)
                      .astype(np.int32), max_new=2)
        self.sched.submit(req)
        while not req.done:
            self.sched.step()
        self.sched.completed.pop(req.uid, None)
        for w in buckets:
            self.decode_once(w)

    def free(self):
        """Delete the program's accelerator state (weights, page pool).
        Arrays on the host CPU device are left to the garbage collector:
        there they may share buffers with the canonical weights."""
        import jax

        for tree in (self.llm.params, self.sched.kv.pcaches):
            for leaf in jax.tree.leaves(tree):
                if all(d.platform != "cpu" for d in leaf.devices()):
                    leaf.delete()
        self.sched = self.llm = None


def label_session(server: Server, buckets: List[int], directory: str):
    """A short trace in which each program kind runs once inside a span
    `bench.label.<kind>`, so the window's trace can tell them apart."""
    import jax

    jax.profiler.start_trace(directory)
    try:
        for w in buckets:
            with jax.profiler.TraceAnnotation("bench.label.decode"):
                server.decode_once(w)
            time.sleep(0.01)
        with jax.profiler.TraceAnnotation("bench.label.prefill"):
            caches1 = server.prefill_once()
        time.sleep(0.01)
        with jax.profiler.TraceAnnotation("bench.label.insert"):
            server.insert_once(caches1)
        time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()


def serve_window(run: Run, sched, reqs: List[T.Request], mix: dict,
                 trace_dir: Optional[str] = None,
                 trace_s: float = 0.0, counter: CompileCounter = None):
    """Send `reqs` as the mix says and turn the scheduler until the
    window has lasted `run.seconds`; fill `run` with what happened.
    With `trace_dir`, profile the window's last `trace_s` seconds: the
    profiler stops once the window has closed, so the seconds it takes
    to write the trace hold up no request of the window."""
    import jax
    from repro.api.scheduler import Request

    clock = time.perf_counter
    ann = jax.profiler.TraceAnnotation
    closed = mix["loop"] == "closed"
    run.requests = []
    start = clock()
    t_nom = start + mix["ramp_s"]
    t_end = t_nom + run.seconds
    run.t_nominal = t_nom
    tr_lo = t_nom + max(0.0, run.seconds - trace_s)
    tr_state = 0                     # 0 not yet, 1 tracing
    tr_ann = None
    inflight: Dict[int, T.Request] = {}
    queue = list(reqs)
    nxt = 0
    w0 = None

    def send(r: T.Request, now: float):
        r.handle = Request(uid=r.rid, prompt=r.prompt, max_new=r.max_new)
        with ann("bench.submit"):
            sched.submit(r.handle)
        r.sent = now
        if r.due is not None:
            run.lateness.append(now - (t_nom + r.due))
        inflight[r.rid] = r
        run.requests.append(r)

    if closed:
        if len(queue) < mix["clients"]:
            raise ValueError("closed-loop pool smaller than its clients")
        for _ in range(mix["clients"]):
            send(queue[nxt], start)
            nxt += 1
    while True:
        now = clock()
        if trace_dir and tr_state == 0 and now >= tr_lo:
            jax.profiler.start_trace(trace_dir)
            tr_ann = ann("bench.traced")
            tr_ann.__enter__()
            tr_state, tr_t0 = 1, clock()
        if not closed:
            while nxt < len(queue) and t_nom + queue[nxt].due <= now:
                send(queue[nxt], now)
                nxt += 1
        if sched.has_work():
            slotted = {id(h) for h in sched.slots if h is not None}
            before = {rid: len(r.handle.out) for rid, r in inflight.items()}
            t0 = clock()
            with ann("bench.step"):
                sched.step()
            t = clock()
            rows = ctx = 0
            prefills = []
            for rid, r in list(inflight.items()):
                h = r.handle
                n = len(h.out)
                gained = n - before[rid]
                dec = gained
                if gained and id(h) not in slotted:
                    prefills.append(len(h.prompt) + before[rid])
                    dec -= 1
                    if r.admitted is None:
                        r.admitted = t0
                if dec > 0:
                    rows += 1
                    ctx += len(h.prompt) + n - 1
                r.token_times.extend([t] * gained)
                if r.first is None and n:
                    r.first = t
                if h.done:
                    del inflight[rid]
                    if closed:
                        if nxt >= len(queue):
                            raise RuntimeError("closed-loop pool exhausted")
                        send(queue[nxt], t)
                        nxt += 1
            m = sched.metrics()
            run.steps.append(Step(t0, t, rows, ctx, prefills,
                                  m.get("pool_pages_used", 0),
                                  m["queue_depth"]))
            if w0 is None and t >= t_nom:
                w0 = t
            if t >= t_end:
                break
        else:
            wake = t_end if closed or nxt >= len(queue) \
                else min(t_nom + queue[nxt].due, t_end)
            if trace_dir and tr_state == 0:
                wake = min(wake, max(tr_lo, now))
            with ann("bench.wait"):
                time.sleep(max(0.0, wake - now))
            t = clock()
            if w0 is None and t >= t_nom:
                w0 = t
            if t >= t_end:
                break
    if tr_state == 1:
        tr_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        run.traced = (tr_t0, clock())
    run.w0, run.w1 = w0, t
    if counter is not None:
        run.compiles_in_window = sum(1 for c in counter.backend_compiles
                                     if w0 <= c <= t)
