"""Megatron-style manual collectives with correct custom-VJP semantics.

The whole framework writes block math ONCE against a named mesh axis
(default "model").  The same code runs under two engines:

  * simulated TP:  ``jax.vmap(fn, axis_name="model")`` over a leading
    (tp, ...) parameter axis — exact math on one CPU device;
  * real TP:       ``jax.shard_map`` over the mesh "model" axis — the
    collectives lower to real all-reduces in the HLO.

Gradients are always taken INSIDE the mapped region (grad-inside-map), so
the shard_map boundary is never differentiated; the three custom-VJP ops
below make Megatron TP math exactly correct in that regime (verified
against single-device autodiff in tests/test_grads.py):

  g_psum          row-parallel output sync:  fwd psum,     bwd identity
  f_ident         column-parallel entry:     fwd identity, bwd psum
  shard_sum_grad  replicated param used in a shard-DIVERGENT region
                  (SPD norm2 / qk-norm / router / SPD bias):
                                             fwd identity, bwd psum

Dropping a sync point (the paper's contribution) = simply not calling
``g_psum`` after the attention output projection; the op is then absent
from the lowered HLO, which the dry-run/roofline accounting verifies.

A trace-time "ledger" records every logical collective with its payload
bytes; `benchmarks/bench_transfer.py` uses it for the paper's Fig-2-style
analytic transfer model and tests assert the SPD byte reduction.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

MODEL_AXIS = "model"
DATA_AXES = ("data",)          # single-pod DP
POD_DATA_AXES = ("pod", "data")  # multi-pod DP


# ---------------------------------------------------------------------------
# Trace-time collective ledger (analytic comm accounting)
# ---------------------------------------------------------------------------


class CommEntry(NamedTuple):
    """One logical collective the ledger recorded.

    op / axis / nbytes   the collective kind, mesh axis name, and payload
                         bytes under the BYTE CONVENTION below
    overlappable         structural property: True for the block sync
                         points SPD could overlap with compute (the kept
                         attention/MLP output reductions and their
                         quantized RS/AG or ring-step decompositions);
                         False for serial-by-construction collectives
                         (embedding lookups, CE softmax sums, the final
                         logits gather).  Whether the time is actually
                         HIDDEN is a backend property — `LatencyModel.
                         summarize(..., overlap=)` prices both readings.
    est_us               modeled wall time of this entry (launch cost +
                         ring wire time) when the capture was opened with
                         `collective_ledger(latency=, tp=)`; 0.0 in plain
                         byte-accounting captures.
    fixed_us             the launch-cost share of est_us (scan-scaled the
                         same way, so `LatencyModel.split_us` can price a
                         body traced once but executed k times without
                         knowing k).  Launches never hide — they are the
                         floor under the exposed time.
    block / phase        attribution labels set by the active
                         `comm_context` when the collective traced:
                         `block` is the model block index the sync
                         belongs to (-1 = unattributed; a scanned
                         segment's entries carry the segment's FIRST
                         block index, since the body traces once at
                         `ledger_scale`-multiplied cost), `phase` is
                         the forward flavor ("prefill" | "decode" |
                         "verify" | "", set by core/model.py).  Both
                         default to the unattributed values, so every
                         pre-existing positional construction and
                         6-field unpacking keeps working.
    """

    op: str
    axis: str
    nbytes: int
    overlappable: bool = False
    est_us: float = 0.0
    fixed_us: float = 0.0
    block: int = -1
    phase: str = ""


def ring_wire_bytes(op: str, payload_bytes: float, n: int) -> float:
    """Bytes ONE device puts on the wire for one logical collective under
    the ring algorithms, given the ledger byte convention (below)."""
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n * payload_bytes
    if op == "reduce-scatter":
        return (n - 1) / n * payload_bytes
    if op == "all-gather":
        return (n - 1) * payload_bytes
    if op == "collective-permute":
        return payload_bytes
    raise ValueError(f"unknown collective op {op!r}")


@dataclass(frozen=True)
class LatencyModel:
    """Analytic per-collective latency: `launch_us` fixed dispatch cost +
    ring wire bytes / `link_bytes_per_s`.  `ring_chunks` is how many ring
    steps an OVERLAPPABLE sync is split into when a backend double-buffers
    it against block compute (parallel/backend.OverlapBackend):

      * a single overlappable entry (a kept exact all-reduce) keeps its
        pipeline-fill chunk and its launch on the critical path —
        exposed = fixed + (T - fixed) / ring_chunks, hidden = the rest
        (clamped at 0: launch-bound tiny syncs can't hide);
      * a collective-permute entry IS one ring step of an overlap-region
        decomposition (compression._log_two_hop) — its transfer rides
        under the double-buffered block compute entirely, only its
        launch stays exposed: hidden = T - fixed.

    Launches never hide either way, which is why the decomposition floors
    its chunk size (MIN_RING_CHUNK_BYTES) instead of always splitting
    ring_chunks-deep.  Defaults model one TPU-v5e ICI link (50 GB/s,
    benchmarks/_common.HW) with a 0.1 us amortized async collective
    launch and 4-deep chunking."""

    link_bytes_per_s: float = 50e9
    launch_us: float = 0.1
    ring_chunks: int = 4

    def collective_us(self, op: str, nbytes: float, n: int) -> float:
        """Serial wall time (us) of one collective of `nbytes` payload."""
        if n <= 1:
            return 0.0
        return (self.launch_us
                + ring_wire_bytes(op, nbytes, n) / self.link_bytes_per_s
                * 1e6)

    def split_us(self, e: "CommEntry") -> tuple:
        """(hidden_us, exposed_us) of one entry when the backend overlaps
        kept syncs; hidden + exposed == e.est_us exactly."""
        if not e.overlappable or self.ring_chunks <= 1:
            return 0.0, e.est_us
        if e.op == "collective-permute":
            hidden = max(e.est_us - e.fixed_us, 0.0)
            return hidden, e.est_us - hidden
        exposed = e.fixed_us + (e.est_us - e.fixed_us) / self.ring_chunks
        hidden = max(e.est_us - exposed, 0.0)
        return hidden, e.est_us - hidden

    def summarize(self, ledger, *, overlap: bool = False) -> dict:
        """Price a latency-annotated capture: {total_us, hidden_us,
        exposed_us, kept_sync_us}.  `overlap=False` (serial backends)
        exposes everything; `overlap=True` hides the chunked fraction of
        every overlappable entry.  `kept_sync_us` is the serial time of
        the overlappable entries alone (the quantity the overlap backend
        is graded on hiding — bench_transfer gates hidden >= 50% of it)."""
        total = hidden = kept = 0.0
        for e in ledger:
            total += e.est_us
            if e.overlappable:
                kept += e.est_us
            if overlap:
                hidden += self.split_us(e)[0]
        return {"total_us": total, "hidden_us": hidden,
                "exposed_us": total - hidden, "kept_sync_us": kept}


class _Ledger(threading.local):
    def __init__(self):
        self.active: Optional[List[CommEntry]] = None
        self.scale: int = 1
        self.latency: Optional[LatencyModel] = None
        self.tp: int = 1

_LEDGER = _Ledger()


@contextmanager
def collective_ledger(latency: Optional[LatencyModel] = None,
                      tp: Optional[int] = None):
    """Capture a `CommEntry` for every logical collective traced inside
    the context.

    BYTE CONVENTION (one convention, everywhere): `nbytes` is the
    PER-DEVICE OPERAND bytes of the collective at its true wire
    precision —

      * all-reduce / reduce-scatter: the full array each device
        contributes (the reduce-scatter's input, NOT its 1/n output);
      * all-gather: the per-device SLICE being gathered (its input);
      * collective-permute: the bytes one device sends in one step.

    Quantized syncs log the int-codes + bf16-scales bytes that actually
    cross the link (compression.wire_bytes), not the fp32 operand the
    CPU emulation reduces; `ring_wire_bytes` converts any entry to
    per-device ring wire traffic.

    `latency=` (with `tp=`, the model-axis degree of the trace) prices
    every entry at capture time — `est_us` = launch + ring-wire /
    bandwidth; without it entries carry est_us=0.0 and remain pure byte
    accounting."""
    if latency is not None and tp is None:
        raise ValueError("collective_ledger(latency=...) needs tp=")
    prev = (_LEDGER.active, _LEDGER.latency, _LEDGER.tp)
    _LEDGER.active, _LEDGER.latency = [], latency
    _LEDGER.tp = int(tp) if tp is not None else 1
    try:
        yield _LEDGER.active
    finally:
        _LEDGER.active, _LEDGER.latency, _LEDGER.tp = prev


@contextmanager
def ledger_scale(k: int):
    """Multiply logged bytes by k while tracing a lax.scan body (the body
    traces once but executes k times — HLO-text op counting has the same
    blind spot, which is why the ledger is the primary byte accounting).
    est_us scales the same way: k executions = k launches + k transfers."""
    prev, _LEDGER.scale = _LEDGER.scale, _LEDGER.scale * int(k)
    try:
        yield
    finally:
        _LEDGER.scale = prev


class _CommCtx(threading.local):
    """Trace-time attribution labels for ledger entries (CommEntry
    block/phase)."""

    def __init__(self):
        self.block: int = -1
        self.phase: str = ""

_COMM_CTX = _CommCtx()


@contextmanager
def comm_context(block: Optional[int] = None, phase: Optional[str] = None):
    """Label every collective traced inside with a block index and/or a
    phase name (CommEntry.block / .phase).  The model wraps each
    segment scan in `comm_context(block=start)` and each forward flavor
    in `comm_context(phase=...)` (core/model.py), so bench curves and
    the obs comm track can attribute wire bytes per layer and per
    serving phase instead of per run.  None leaves the outer value in
    place (contexts nest)."""
    prev = (_COMM_CTX.block, _COMM_CTX.phase)
    if block is not None:
        _COMM_CTX.block = int(block)
    if phase is not None:
        _COMM_CTX.phase = str(phase)
    try:
        yield
    finally:
        _COMM_CTX.block, _COMM_CTX.phase = prev


def comm_phase(phase: str):
    """Shorthand: `comm_context(phase=...)`."""
    return comm_context(phase=phase)


def _append(op: str, axis, nbytes: int, overlappable: bool) -> None:
    name = axis if isinstance(axis, str) else "+".join(axis)
    est = fixed = 0.0
    if _LEDGER.latency is not None and _LEDGER.tp > 1:
        est = _LEDGER.scale * _LEDGER.latency.collective_us(
            op, nbytes, _LEDGER.tp)
        fixed = _LEDGER.scale * _LEDGER.latency.launch_us
    _LEDGER.active.append(CommEntry(op, name, int(nbytes) * _LEDGER.scale,
                                    overlappable, est, fixed,
                                    _COMM_CTX.block, _COMM_CTX.phase))


def _log(op: str, axis, x, *, overlappable: bool = False) -> None:
    if _LEDGER.active is None:
        return
    leaves = jax.tree_util.tree_leaves(x)
    nbytes = sum(l.size * l.dtype.itemsize for l in leaves)
    _append(op, axis, nbytes, overlappable)


def log_collective(op: str, axis, nbytes: int, *,
                   overlappable: bool = False) -> None:
    """Ledger entry with an EXPLICIT byte count — for collectives whose
    wire format differs from their operand (quantized payloads log the
    int8/int4+scales bytes that actually cross the link, not the fp32
    operand the CPU emulation reduces)."""
    if _LEDGER.active is None:
        return
    _append(op, axis, int(nbytes), overlappable)


# ---------------------------------------------------------------------------
# Overlap regions (trace-time): chunked-ring sync accounting
# ---------------------------------------------------------------------------


class _Overlap(threading.local):
    def __init__(self):
        self.chunks: int = 0          # 0 = not inside an overlap region

_OVERLAP = _Overlap()


@contextmanager
def overlap_region(chunks: int = 4):
    """Trace-time marker the overlap backend wraps every step in: while
    active, each kept QUANTIZED sync logs its two hops as `chunks`
    ring-step collective-permute entries (bytes identical in total to
    the RS/AG pair — the decomposition XLA would pipeline against the
    block's MLP on a real interconnect), and kept exact syncs stay
    single all-reduce entries flagged overlappable.  Execution is
    UNCHANGED — same psum, bit-identical outputs — this is the ledger
    seam of the CPU emulation (compression.py module docstring); the
    runnable ppermute ring lives in compression.ring_* and is
    unit-tested against the fused collectives."""
    prev, _OVERLAP.chunks = _OVERLAP.chunks, int(chunks)
    try:
        yield
    finally:
        _OVERLAP.chunks = prev


def overlap_chunks() -> int:
    """Ring-chunk count of the active overlap region (0 outside one)."""
    return _OVERLAP.chunks


# ---------------------------------------------------------------------------
# Custom-VJP collectives
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def g_psum(x, axis):
    """Row-parallel output sync: y = Σ_shards x.  Backward = identity
    (the replicated cotangent is what every shard's partial receives)."""
    return jax.lax.psum(x, axis)


def _g_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _g_bwd(axis, _, ct):
    return (ct,)


g_psum.defvjp(_g_fwd, _g_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def f_ident(x, axis):
    """Column-parallel region entry on a replicated activation: identity
    forward, psum backward (accumulates per-shard cotangents)."""
    return x


def _f_fwd(x, axis):
    return x, None


def _f_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


f_ident.defvjp(_f_fwd, _f_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def shard_sum_grad(p, axis):
    """Mark a REPLICATED parameter used inside a shard-divergent region.

    fwd identity; bwd psum — the parameter's true gradient is the sum of
    the per-shard partials.  (In replicated regions the cotangent is
    already full; use the parameter directly there.)"""
    return p


def _s_fwd(p, axis):
    return p, None


def _s_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


shard_sum_grad.defvjp(_s_fwd, _s_bwd)


# ---------------------------------------------------------------------------
# Logged wrappers (the model calls these; ledger sees every sync point)
# ---------------------------------------------------------------------------

class _SyncMode(threading.local):
    def __init__(self):
        self.mode: str = "exact"     # exact | int8 | int4

_SYNC = _SyncMode()


@contextmanager
def sync_compression(mode: str):
    """Beyond-paper optimization (cf. Dong et al. 2024, low-bit TP
    communication, cited by the paper): while tracing with mode="int8",
    every KEPT sync point that does not carry an EXPLICIT per-block mode
    (an SPDPlanConfig.comm policy) quantizes its partial to int8/int4 via
    compression.quantized_psum.  The per-block CommPolicy is the primary
    mechanism; this context remains as the blanket trace-time override
    (dryrun --sync-q8).  Inference paths only (round() passes gradients
    straight-through)."""
    prev, _SYNC.mode = _SYNC.mode, mode
    try:
        yield
    finally:
        _SYNC.mode = prev


# accepted spellings of the sync levels ("quantN" from config.CommPolicy,
# "intN" from the legacy sync_compression context)
_MODE_BITS = {"int8": 8, "quant8": 8, "int4": 4, "quant4": 4}


def sync_output(x, axis=MODEL_AXIS, compressible: bool = True, mode=None):
    """A sync point: the all-reduce after a row-parallel projection.
    THIS is the op SPD drops.  `mode` is the block's kept-sync level from
    its CommPolicy ("exact" | "quant8" | "quant4"; None defers to the
    sync_compression context).  `compressible=False` pins exact reduction
    (embedding lookup, CE softmax sums — tiny payloads, precision-bound).
    Its ops carry the name scope `sync.b<block>`, `<block>` being the
    first block of the segment scan it is traced in (`comm_context`), or
    `sync` outside any segment, so a device trace can find each kept
    sync: inside one execution of a segment's layer scan the k-th event
    of a scoped op is block <block> + k."""
    m = mode if mode is not None else _SYNC.mode
    blk = _COMM_CTX.block
    with jax.named_scope("sync" if blk < 0 else f"sync.b{blk}"):
        if compressible and m in _MODE_BITS:
            from repro.parallel.compression import quantized_psum
            return quantized_psum(x, axis, bits=_MODE_BITS[m])
        # a compressible kept sync is exactly the class of collective the
        # overlap backend can double-buffer against block compute; pinned
        # exact reductions (embedding, CE) are serial by construction
        _log("all-reduce", axis, x, overlappable=compressible)
        return g_psum(x, axis)


def column_entry(x, axis=MODEL_AXIS):
    return f_ident(x, axis)


def shared_param(p, axis=MODEL_AXIS):
    return shard_sum_grad(p, axis)


def pmax(x, axis=MODEL_AXIS):
    _log("all-reduce", axis, x)   # max all-reduce, same payload
    return jax.lax.pmax(x, axis)


def psum_plain(x, axis):
    """Non-differentiated psum (gradient reductions, metrics)."""
    _log("all-reduce", axis, x)
    return jax.lax.psum(x, axis)


def psum_scatter(x, axis, **kw):
    _log("reduce-scatter", axis, x)
    return jax.lax.psum_scatter(x, axis, **kw)


def all_gather(x, axis_name, **kw):
    _log("all-gather", axis_name, x)
    return jax.lax.all_gather(x, axis_name, **kw)


def ppermute(x, axis, perm):
    _log("collective-permute", axis, x)
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def axis_size(axis=MODEL_AXIS) -> int:
    if hasattr(jax.lax, "axis_size"):
        return jax.lax.axis_size(axis)
    # JAX 0.4.x: no jax.lax.axis_size; a psum of ones is the same value
    # (constant-folded, no collective emitted for the ledger).
    return jax.lax.psum(1, axis)
