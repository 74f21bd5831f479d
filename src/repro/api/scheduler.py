"""The one continuous-batching scheduler behind the `repro.api` facade.

Historically the runtime had two near-duplicate schedulers — a dense
`Server` (fixed per-slot caches) and a `PagedServer` (page-pool
admission + preemption-by-eviction).  They are collapsed here into a
single `Scheduler` driven by a `CacheConfig`: dense is simply the
`page_size=num_pages=None` degenerate case, realized by a pluggable
`KVCacheManager` (`DenseKVCacheManager` / `PagedKVCacheManager`).  The
deprecated `repro.runtime.server` shims over this class were deleted
in PR 5 — this IS the serving entrypoint.

Engine contract (the unified runtime/engines.py Engine, identical on
every registered parallel backend — docs/architecture.md):
    prefill / prefill_chunked            -> (logits, caches1)
    decode / decode_sampled              dense decode step
    decode_paged / decode_paged_sampled  paged decode step
    blank_caches / blank_paged_caches, insert_slot / insert_paged

Sampling: every token goes through the jitted sampling step in
`repro.runtime.sampling`, honoring each request's `SamplingParams`
(greedy, temperature, top-k, top-p, per-request seed, stop tokens,
max_new).  A batch whose active requests are all greedy uses the
engines' fused greedy decode (bit-identical to the pre-facade servers);
any sampled request switches the step to the sampled decode path.

Admission is validated up front (`InvalidRequestError`: empty prompt,
non-positive max_new, prompt + max_new beyond per-slot or pool
capacity) instead of failing later with shape errors inside
`insert_slot` / `scatter_prefill_pages`.

Scheduling semantics (unchanged from the pre-facade servers; full
design in docs/serving.md):
  * dense: admit whenever a slot is free, FIFO;
  * paged: head-of-line FIFO admission against free PAGES; before each
    decode step every active slot must own the page it is about to
    write, and pool exhaustion preempts the latest-admitted slot
    (pages freed, request requeued at the front keeping its generated
    tokens; on re-admission it prefills over prompt + output).
Chunked prefill (`CacheConfig.prefill_chunk`) now applies to BOTH cache
layouts — the dense path used to silently ignore it.

Speculative decoding (docs/speculative.md): constructed with a
`repro.spec.SpecState`, every decode step becomes a draft-k /
verify-once round — the Drafter proposes k tokens with the target's own
weights under a cheap comm plan, ONE multi-token verify forward scores
them, and acceptance (greedy or rejection-sampled) commits 1..k+1
tokens.  Rejected suffixes roll back: dense caches rewind the position
counter, paged slots return their suffix pages (`PagePool.shrink`).
Greedy streams stay bit-identical to plain decoding.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.api.sampling import SamplingParams
from repro.obs.recorder import NULL_RECORDER
from repro.runtime import sampling as RS
from repro.runtime.paging import PagePool
from repro.spec.verify import (accept_greedy_tree, accept_speculative_tree,
                               filtered_probs, spec_rng, tree_layout)

__all__ = ["CacheConfig", "Request", "Scheduler", "InvalidRequestError",
           "SchedulerError", "DenseKVCacheManager", "PagedKVCacheManager"]

_GREEDY = SamplingParams()

# spec acceptance-rate histogram layout (a 0..1 ratio, not seconds)
_ACCEPT_BUCKETS = (0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


class SchedulerError(RuntimeError):
    """Internal scheduling invariant violated."""


class InvalidRequestError(ValueError):
    """Request rejected at admission (subclasses ValueError so legacy
    `except ValueError` call sites keep working)."""


@dataclass(frozen=True)
class CacheConfig:
    """KV-cache geometry for a `Scheduler`.

    Dense layout when `page_size`/`num_pages` are None; paged otherwise
    (both must be set together).  `prefill_chunk` switches prompt
    prefill from power-of-two buckets to fixed-size chunks on either
    layout.
    """

    cache_len: int
    max_batch: int = 4
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    prefill_chunk: Optional[int] = None
    # prefix caching (paged only): None = auto — enabled when the engine
    # and arch support the fused paged forward (model.supports_paged_
    # attention), since suffix prefill runs through verify_paged and COW
    # through copy_paged_pages.  True forces it on, False off.
    prefix_cache: Optional[bool] = None

    def __post_init__(self):
        if self.cache_len <= 0 or self.max_batch <= 0:
            raise ValueError(f"bad cache geometry: {self}")
        if (self.page_size is None) != (self.num_pages is None):
            raise ValueError(
                "page_size and num_pages must be set together "
                f"(got page_size={self.page_size}, "
                f"num_pages={self.num_pages})")
        if self.paged:
            if self.page_size <= 0 or self.num_pages <= 0:
                raise ValueError(f"bad paged geometry: {self}")
            if self.cache_len % self.page_size:
                raise ValueError(
                    f"cache_len={self.cache_len} not a multiple of "
                    f"page_size={self.page_size}")
        if self.prefill_chunk is not None and self.prefill_chunk <= 0:
            raise ValueError(f"prefill_chunk must be positive: {self}")

    @property
    def paged(self) -> bool:
        return self.page_size is not None


@dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new: int = 16
    eos: int = -1                   # -1 => never
    out: List[int] = field(default_factory=list)
    done: bool = False
    n_preempted: int = 0
    # new fields AFTER every legacy one, so pre-facade positional
    # construction keeps binding the same way
    sampling: Optional[SamplingParams] = None
    finish_reason: Optional[str] = None
    # speculative-decoding stats (docs/speculative.md): tokens drafted
    # for this request and how many the verify forward accepted
    n_drafted: int = 0
    n_draft_accepted: int = 0


# ---------------------------------------------------------------------------
# KV-cache managers: the layout-specific half of the scheduler
# ---------------------------------------------------------------------------


class DenseKVCacheManager:
    """One fixed `cache_len` stripe per slot; capacity is per-slot only."""

    paged = False

    def __init__(self, engine, cc: CacheConfig):
        self.engine = engine
        self.cc = cc
        self.caches = engine.blank_caches(cc.max_batch, cc.cache_len)

    def capacity_error(self, prompt_len: int, max_new: int) -> Optional[str]:
        # dense slots only ever hold prompt + one KV write per decode
        # step except the last (the final token's KV is never stored)
        need = prompt_len + max_new - 1
        if need > self.cc.cache_len:
            return (f"request needs {need} cache positions, exceeding "
                    f"per-slot cache_len={self.cc.cache_len}")
        return None

    def can_admit(self, slot: int, total: int) -> bool:
        return True                       # slot freeness is checked upstream

    def admit_begin(self, slot: int, toks, total: int) -> Optional[int]:
        """Reserve capacity for admission; returns the number of prompt
        tokens already resident (always 0 — dense slots never share),
        or None when admission must wait.  Mirrors the paged manager so
        the scheduler has one admission flow."""
        return 0

    def register_prefix(self, slot: int, toks):
        pass                              # no prefix index on dense slots

    # prefix-cache stats (always zero on dense — kept for uniform reporting)
    prefix_queries = 0
    prefix_hits = 0
    prefix_tokens_reused = 0

    def ensure(self, slot: int, upto: int) -> bool:
        return upto <= self.cc.cache_len

    def insert(self, caches1, slot: int):
        self.caches = self.engine.insert_slot(self.caches, caches1, slot)

    def release(self, slot: int):
        pass

    def decode(self, params, cur, pos):
        nxt, self.caches = self.engine.decode(params, cur, pos, self.caches)
        return nxt

    def decode_sampled(self, params, cur, pos, t, k, p, keys):
        nxt, self.caches = self.engine.decode_sampled(
            params, cur, pos, self.caches, t, k, p, keys)
        return nxt

    def verify(self, params, toks, pos, tree=None):
        """Multi-token speculative verify -> full logits (B, C, V),
        returned as the engine's device array (callers fetch only what
        they need — all-greedy rounds pull just the argmax ids).
        `tree=(depths, anc)` verifies a draft tree chunk (kept off the
        call when None so chain rounds hit the same compiled step as
        before, and stub engines never see the kwarg)."""
        if tree is None:
            lg, self.caches = self.engine.verify(params, toks, pos,
                                                 self.caches)
        else:
            lg, self.caches = self.engine.verify(params, toks, pos,
                                                 self.caches, tree=tree)
        return lg

    def copy_pos(self, src, dst):
        """Per-row cache position copy src[b] -> dst[b] (tree rounds
        relocate an accepted alternative's KV from its chunk slot to the
        committed stream position).  No-op on engines without the step
        (test stubs track tokens, not KV)."""
        cp = getattr(self.engine, "copy_pos", None)
        if cp is not None:
            self.caches = cp(self.caches, src, dst)

    def truncate(self, slot: int, n_tokens: int):
        # dense rollback of a rejected speculative suffix is free: the
        # stale KV past the committed position is causally masked and
        # overwritten as the position counter passes it again
        pass


class PagedKVCacheManager:
    """Page-pool allocator + page tables (runtime/paging.py), plus the
    prefix cache: admission matches a new prompt's full pages against
    resident registered pages, shares the hit read-only (refcounts), and
    prefills only the uncached suffix through `verify_paged` with every
    other batch row masked to the trash page."""

    paged = True

    def __init__(self, engine, cc: CacheConfig):
        self.engine = engine
        self.cc = cc
        self.pool = PagePool(num_pages=cc.num_pages, page_size=cc.page_size,
                             max_slots=cc.max_batch,
                             pages_per_slot=cc.cache_len // cc.page_size)
        self.pcaches = engine.blank_paged_caches(
            cc.max_batch, cc.cache_len, page_size=cc.page_size,
            num_pages=cc.num_pages)
        self.prefix_cache = cc.prefix_cache
        if self.prefix_cache is None:
            # auto: needs the fused paged forward (suffix prefill rides
            # verify_paged) and the COW copy step; engines without a cfg
            # (test fakes) and uncovered archs stay cold-path only
            cfg = getattr(engine, "cfg", None)
            if cfg is None or not hasattr(engine, "copy_paged_pages"):
                self.prefix_cache = False
            else:
                from repro.core.model import supports_paged_attention
                self.prefix_cache = supports_paged_attention(cfg)
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # per-slot chain digests computed at admission (one pass over the
        # prompt, runtime/paging.page_hashes) and reused by
        # register_prefix — each admitted prompt is hashed exactly once
        self._admit_hashes: Dict[int, list] = {}

    def _table(self, rows=None):
        """Device page table, width-bucketed to the next power of two of
        the largest row (fewer K/V positions to attend over; powers of
        two keep XLA's reduction trees associating the valid prefix
        identically, so bucketing never changes tokens — and bound the
        compile count to log2(pages_per_slot) variants)."""
        t = self.pool.table if rows is None else rows
        w = max(1, int(self.pool.owned.max()))
        b = 1
        while b < w:
            b <<= 1
        return jnp.asarray(t[:, :min(b, self.pool.pages_per_slot)])

    def _cow(self, pos, n_tokens: int):
        """Copy-on-write barrier before writing n_tokens at pos[b]:
        every page about to be written must be privately owned.  In the
        steady state this never copies (writes sit above any shared
        prefix); it exists so sharing can never corrupt another slot."""
        pairs = []
        ps = self.cc.page_size
        for b in range(self.cc.max_batch):
            own = int(self.pool.owned[b])
            if own == 0:
                continue
            lo = int(pos[b]) // ps
            hi = min((int(pos[b]) + n_tokens - 1) // ps, own - 1)
            for pg in range(lo, hi + 1):
                pr = self.pool.ensure_writable(b, pg)
                if pr is not None:
                    pairs.append(pr)
        if pairs:
            src, dst = zip(*pairs)
            self.pcaches = self.engine.copy_paged_pages(
                self.pcaches, list(src), list(dst))

    def capacity_error(self, prompt_len: int, max_new: int) -> Optional[str]:
        # paged admission unconditionally grows to resume_len + 1, and a
        # preemption after max_new - 1 tokens resumes with prompt +
        # max_new - 1 tokens — so the worst case really is prompt +
        # max_new positions (the legacy PagedServer bound); anything
        # looser can livelock the FIFO head after a late preemption
        need = prompt_len + max_new
        if need > self.cc.cache_len or not self.pool.fits_alone(need):
            return (f"request needs {need} cache positions, exceeding "
                    f"pool capacity ({self.pool.num_pages} pages x "
                    f"{self.pool.page_size} tokens, "
                    f"cache_len={self.cc.cache_len})")
        return None

    def can_admit(self, slot: int, total: int) -> bool:
        return self.pool.grow(slot, total)

    def admit_begin(self, slot: int, toks, total: int) -> Optional[int]:
        """Match the prompt against the prefix cache, share the hit, and
        reserve pages through `total` positions.  Returns the number of
        resident prefix tokens (0 = cold admission, full prefill), or
        None when the pool cannot supply the pages (head-of-line wait).
        The match is capped page-aligned BELOW len(toks) so at least one
        position is always prefilled — logits for the first sampled
        token must come from a real forward."""
        matched = []
        self._admit_hashes.pop(slot, None)   # drop any stale admission
        if self.prefix_cache and len(toks) > 1:
            from repro.runtime.paging import page_hashes
            ps = self.cc.page_size
            self.prefix_queries += 1
            # hash the whole prompt's full pages in one pass; the chain
            # property makes the first cap/ps digests exactly the capped
            # prefix's digests, and register_prefix reuses the rest
            hashes = page_hashes(np.asarray(toks), ps)
            self._admit_hashes[slot] = hashes
            cap_pages = (len(toks) - 1) // ps
            if cap_pages > 0:
                matched = self.pool.match_prefix(
                    None, hashes=hashes[:cap_pages])
        if matched:
            self.pool.share_prefix(slot, matched)
        if not self.pool.grow(slot, total):
            self.pool.release(slot)
            return None
        if matched:
            self.prefix_hits += 1
            self.prefix_tokens_reused += len(matched) * self.cc.page_size
        return len(matched) * self.cc.page_size

    def register_prefix(self, slot: int, toks):
        """Index the slot's full prompt pages for future sharing (digests
        reused from admission — the prompt was hashed once there)."""
        if self.prefix_cache:
            self.pool.register_prefix(slot, np.asarray(toks),
                                      hashes=self._admit_hashes.pop(
                                          slot, None))

    def prefill_suffix(self, params, toks, m: int, slot: int):
        """Prefill tokens[m:] into `slot`'s own pages (positions m..s-1)
        through the paged verify step, with every OTHER row's table
        masked to -1 (their reads hit the fully-masked trash page, their
        writes land in it — live slots untouched).  The suffix is
        right-padded to a power-of-two bucket; pad positions' K/V land
        above s in the slot's reserved pages (or the trash page) and are
        overwritten by decode before ever becoming causally visible.
        Returns full-vocab logits (1, V) for position s-1."""
        toks = np.asarray(toks, np.int32)
        s = toks.shape[0]
        ln = s - m
        assert ln >= 1, (s, m)
        sb = max(8, 1 << (ln - 1).bit_length())
        n = self.cc.max_batch
        tok_arr = np.zeros((n, sb), np.int32)
        tok_arr[slot, :ln] = toks[m:]
        pos = np.zeros(n, np.int32)
        pos[slot] = m
        rows = np.full_like(self.pool.table, -1)
        rows[slot] = self.pool.table[slot]
        lg, self.pcaches = self.engine.verify_paged(
            params, jnp.asarray(tok_arr), jnp.asarray(pos),
            self._table(rows), self.pcaches)
        return jnp.asarray(lg)[slot:slot + 1, ln - 1]

    def ensure(self, slot: int, upto: int) -> bool:
        return self.pool.grow(slot, upto)

    def insert(self, caches1, slot: int):
        self.pcaches = self.engine.insert_paged(
            self.pcaches, caches1, slot, self.pool.table[slot])

    def release(self, slot: int):
        self.pool.release(slot)

    def _decode_inputs(self, pos):
        """COW barrier and page table of a decode step, each timed as a
        span of the pool (`pool.cow`, `pool.table`)."""
        obs = self.pool.obs
        with obs.span("pool", "cow"):
            self._cow(np.asarray(pos), 1)
        with obs.span("pool", "table"):
            return self._table()

    def decode(self, params, cur, pos):
        table = self._decode_inputs(pos)
        nxt, self.pcaches = self.engine.decode_paged(
            params, cur, pos, table, self.pcaches)
        return nxt

    def decode_sampled(self, params, cur, pos, t, k, p, keys):
        table = self._decode_inputs(pos)
        nxt, self.pcaches = self.engine.decode_paged_sampled(
            params, cur, pos, table, self.pcaches, t, k, p, keys)
        return nxt

    def verify(self, params, toks, pos, tree=None):
        self._cow(np.asarray(pos), int(toks.shape[1]))
        if tree is None:
            lg, self.pcaches = self.engine.verify_paged(
                params, toks, pos, self._table(), self.pcaches)
        else:
            lg, self.pcaches = self.engine.verify_paged(
                params, toks, pos, self._table(), self.pcaches, tree=tree)
        return lg

    def copy_pos(self, src, dst):
        """Tree alt-KV relocation through the page table; MUST run
        before `truncate` frees the pages holding the chunk slots.  The
        destination page sits inside the verify chunk's write region, so
        this round's COW barrier already made it privately owned."""
        cp = getattr(self.engine, "copy_pos_paged", None)
        if cp is not None:
            self.pcaches = cp(self.pcaches, self._table(), src, dst,
                              page_size=self.cc.page_size)

    def truncate(self, slot: int, n_tokens: int):
        # paged rollback: pages past the committed length drop their
        # reference (table keeps its valid-prefix/-1-suffix invariant)
        self.pool.shrink(slot, n_tokens)


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------


class Scheduler:
    """Continuous batching over either cache layout (see module doc)."""

    def __init__(self, engine, params, cache: CacheConfig, spec=None,
                 obs=None):
        self.engine = engine
        self.params = params
        self.cache = cache
        self.kv = (PagedKVCacheManager(engine, cache) if cache.paged
                   else DenseKVCacheManager(engine, cache))
        self.max_batch = cache.max_batch
        self.cache_len = cache.cache_len
        self.prefill_chunk = cache.prefill_chunk
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * cache.max_batch
        self.pos = np.zeros(cache.max_batch, np.int32)
        self.cur = np.zeros((cache.max_batch, 1), np.int32)
        self.admit_seq = np.zeros(cache.max_batch, np.int64)
        self._seq = 0
        self.completed: Dict[int, Request] = {}
        self.n_preemptions = 0
        # speculative decoding (repro.spec.SpecState or None): when set,
        # decode steps become draft-k / verify-once rounds that can
        # commit several tokens at a time (docs/speculative.md)
        self.spec = spec
        self.spec_rounds = 0          # verify forwards executed
        self.spec_row_rounds = 0      # sum of active rows over rounds
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_committed = 0       # tokens committed by spec rounds
        self.spec_alt_commits = 0     # tree rounds committed via an alt
        # per-slot adaptive draft budget + zero-acceptance streak
        # (SpecConfig adaptive/k_min/k_max; reset at admission)
        self._spec_kb = np.zeros(cache.max_batch, np.int32)
        self._spec_rej = np.zeros(cache.max_batch, np.int32)
        # observability (repro.obs): the default NULL_RECORDER makes
        # every hook below a no-op — timestamps are only read and
        # request metadata only kept when a live Recorder is attached,
        # so disabled observability is zero-cost and cannot perturb
        # tokens (hooks never touch device arrays either way)
        self.obs = NULL_RECORDER
        self._req_meta: Dict[int, dict] = {}   # id(Request) -> times
        if obs is not None:
            self.set_obs(obs)

    def set_obs(self, obs):
        """Attach/detach a recorder on the scheduler and everything it
        drives (page pool, drafter).  Returns the previous recorder —
        replicas swap in NULL_RECORDER around warm-up so synthetic
        requests never pollute metrics or traces."""
        prev = self.obs
        self.obs = obs if obs is not None else NULL_RECORDER
        if self.kv.paged:
            self.kv.pool.obs = self.obs
        if self.spec is not None:
            self.spec.drafter.obs = self.obs
        return prev

    def metrics(self) -> dict:
        """Scheduler-level stats (always available) plus, with a live
        recorder attached, the flat metrics-registry snapshot under
        `"registry"` (docs/observability.md)."""
        out = {
            "queue_depth": len(self.queue),
            "active_slots": len(self._active()),
            "completed": len(self.completed),
            "n_preemptions": self.n_preemptions,
            "prefix_queries": self.kv.prefix_queries,
            "prefix_hits": self.kv.prefix_hits,
            "prefix_tokens_reused": self.kv.prefix_tokens_reused,
        }
        if self.kv.paged:
            pool = self.kv.pool
            out["pool_pages_used"] = (pool.num_pages - len(pool.free)
                                      - len(pool.cached))
            out["pool_high_water"] = pool.high_water
        if self.spec is not None:
            out["spec_rounds"] = self.spec_rounds
            out["spec_acceptance"] = self.spec_acceptance
            out["spec_tokens_per_step"] = self.spec_tokens_per_step
            out["spec_alt_commits"] = self.spec_alt_commits
        if self.obs.enabled:
            out["registry"] = self.obs.snapshot()
        return out

    # legacy attribute names (pre-facade Server/PagedServer)
    @property
    def max_slots(self) -> int:
        return self.max_batch

    @property
    def caches(self):
        return self.kv.caches

    @property
    def pcaches(self):
        return self.kv.pcaches

    @property
    def pool(self) -> PagePool:
        return self.kv.pool

    # ---------------- request lifecycle ----------------

    def submit(self, req: Request):
        """Validate and enqueue.  Raises InvalidRequestError on requests
        that could never run (instead of shape failures downstream)."""
        self.validate(req)
        self.note_submit(req)
        self.queue.append(req)

    def note_submit(self, req: Request):
        """Stamp a request's submission time (queue-wait / TTFT base).
        `submit()` calls this; callers that enqueue directly (the facade
        batches validation) should call it themselves — un-stamped
        requests are back-filled at admission with zero queue wait."""
        if self.obs.enabled:
            t = self.obs.now()
            meta = self._req_meta.setdefault(
                id(req), {"submit0": t, "first": None})
            meta["submit"] = t
            self.obs.inc("requests_submitted_total")

    def validate(self, req: Request):
        """Admission checks only — raises InvalidRequestError, enqueues
        nothing (callers batching submissions validate up front)."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise InvalidRequestError(
                f"request {req.uid}: prompt must be a non-empty 1-D token "
                f"array (got shape {prompt.shape})")
        if req.max_new <= 0:
            raise InvalidRequestError(
                f"request {req.uid}: max_new must be positive "
                f"(got {req.max_new})")
        if len(prompt) > self.cache_len:
            raise InvalidRequestError(
                f"request {req.uid}: prompt length {len(prompt)} exceeds "
                f"cache_len={self.cache_len}")
        msg = self.kv.capacity_error(len(prompt), self._max_new(req))
        if msg is not None:
            raise InvalidRequestError(f"request {req.uid}: {msg}")

    @staticmethod
    def _resume_tokens(req: Request) -> np.ndarray:
        """Prompt plus already-generated tokens (recompute after preempt)."""
        if not req.out:
            return np.asarray(req.prompt, np.int32)
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(req.out, np.int32)])

    def _prefill(self, toks: np.ndarray, s: int):
        # shared with the speculative Drafter's admission prefill:
        # chunked when configured, else right-padded to a power-of-two
        # bucket capped at the slot capacity (exact — decode overwrites
        # pad slots before they are causally visible)
        from repro.runtime.engines import bucketed_prefill
        return bucketed_prefill(self.engine, self.params, toks, s,
                                self.cache_len, self.prefill_chunk)

    def _first_token(self, req: Request, logits) -> int:
        """Sample the admission token from the prefill logits via the
        jitted sampling step (greedy == argmax, as before)."""
        sp = req.sampling or _GREEDY
        keys = RS.make_keys(np.asarray([sp.seed], np.int32),
                            np.asarray([len(req.out)], np.int32))
        tok = RS.sample_tokens(
            jnp.asarray(logits), np.asarray([sp.temperature], np.float32),
            np.asarray([sp.top_k], np.int32),
            np.asarray([sp.top_p], np.float32), keys)
        return int(np.asarray(tok)[0])

    def _admit(self):
        for b in range(self.max_batch):
            if not self.queue:
                break
            if self.slots[b] is not None:
                continue
            req = self.queue[0]
            toks = self._resume_tokens(req)
            s = len(toks)
            # prefix-cache match + capacity for the prompt + the first
            # decode write at pos s; m = resident prefix tokens (0=cold)
            m = self.kv.admit_begin(b, toks, s + 1)
            if m is None:
                break          # head-of-line: wait for pages, stay FIFO
            self.queue.popleft()
            # the span covers the admission from its reservation on:
            # prefill, first-token pull, pool insert; its two clock reads
            # also bound the queue and prefill slices
            with self.obs.span("scheduler", "admit") as sp:
                if self.obs.enabled:
                    sp.update(uid=req.uid, slot=b, tokens=s - m, cached=m)
                    meta = self._req_meta.setdefault(
                        id(req),
                        {"submit0": sp.t0, "submit": sp.t0, "first": None})
                    wait = sp.t0 - meta["submit"]
                    self.obs.observe("queue_wait_seconds", wait)
                    self.obs.complete(f"slot{b}", "queue", meta["submit"],
                                      wait, uid=req.uid)
                first, t_first = self._admit_one(b, req, toks, s, m)
            if self.obs.enabled:
                meta["serve_start"] = sp.t0
                self.obs.complete(f"slot{b}", "prefill", sp.t0,
                                  sp.t1 - sp.t0, uid=req.uid,
                                  tokens=s - m, cached=m)
                if meta["first"] is None:
                    # TTFT is measured once, from the ORIGINAL submit
                    # (re-admissions after preemption don't re-count)
                    meta["first"] = t_first
                    self.obs.observe("ttft_seconds",
                                     t_first - meta["submit0"])
                if m:
                    self.obs.inc("prefix_cache_hits_total")
                    self.obs.inc("prefix_tokens_reused_total", m)
            if self._stopping(req, first):
                self._finish(b)

    def _admit_one(self, b: int, req: Request, toks: np.ndarray, s: int,
                   m: int) -> Tuple[int, Optional[float]]:
        """Prefill `req` into slot b (its pages reserved, `m` prefix
        tokens resident), put it in the batch and the pool; returns its
        first token and, when recording, the clock read once that token
        is on the host (the TTFT's end: before the pool insert and the
        drafter's admission)."""
        try:
            if m:
                # warm admission: shared prefix pages are already
                # resident — prefill only the uncached suffix in place
                # (no dense caches1 / insert round-trip)
                logits = self.kv.prefill_suffix(self.params, toks, m, b)
            else:
                logits, caches1 = self._prefill(toks, s)
            first = self._first_token(req, logits)
        except BaseException:
            # admit_begin already reserved pages for slot b — free them
            # and put the request back so nothing leaks on a prefill
            # failure (engine error, interrupt, ...)
            self.kv.release(b)
            self.queue.appendleft(req)
            raise
        req.out.append(first)
        self.slots[b] = req
        self.pos[b] = s
        self.cur[b, 0] = first
        self.admit_seq[b] = self._seq
        self._seq += 1
        t_first = self.obs.now() if self.obs.enabled else None
        if not m:
            self.kv.insert(caches1, b)
        self.kv.register_prefix(b, toks)
        if self.spec is not None:
            # the draft shares weights, not caches — but a COLD admission
            # just prefilled this exact prompt, and the drafter can
            # restack that KV onto its own plan instead of re-prefilling
            # (Drafter.insert documents the adoption contract; warm
            # admissions have no dense caches1, so the drafter prefills
            # itself)
            self._spec_kb[b] = self.spec.k
            self._spec_rej[b] = 0
            try:
                self.spec.drafter.insert(
                    b, toks, caches1=None if m else caches1)
            except TypeError:
                # legacy drafter stubs without the adoption kwarg
                self.spec.drafter.insert(b, toks)
        return first, t_first

    @staticmethod
    def _max_new(req: Request) -> int:
        """Effective decode budget: the tighter of Request.max_new and
        the request's SamplingParams.max_new (so both documented knobs
        are honored for direct submit() users; the facade sets them
        equal)."""
        if req.sampling is None:
            return req.max_new
        return min(req.max_new, req.sampling.max_new)

    def _stopping(self, req: Request, tok: int) -> bool:
        sp = req.sampling
        if tok == req.eos or (sp is not None and tok in sp.stop_token_ids):
            req.finish_reason = "stop"
            return True
        if len(req.out) >= self._max_new(req):
            req.finish_reason = "length"
            return True
        return False

    def _finish(self, b: int):
        req = self.slots[b]
        req.done = True
        if self.obs.enabled:
            t = self.obs.now()
            meta = self._req_meta.pop(id(req), None)
            reason = req.finish_reason or "stop"
            self.obs.inc("requests_finished_total", reason=reason)
            self.obs.inc("tokens_generated_total", len(req.out))
            if req.n_drafted:
                # per-request draft acceptance over the whole lifetime
                # (the round-level spec_acceptance_ratio histogram sees
                # every round; this one sees every request)
                self.obs.metrics.observe(
                    "spec_request_acceptance",
                    req.n_draft_accepted / req.n_drafted,
                    buckets=_ACCEPT_BUCKETS)
            if meta is not None:
                if meta.get("first") is not None and len(req.out) > 1:
                    # time-per-output-token over the decode tail (the
                    # first token is TTFT's, not TPOT's)
                    self.obs.observe(
                        "tpot_seconds",
                        (t - meta["first"]) / (len(req.out) - 1))
                t0 = meta.get("serve_start", t)
                self.obs.complete(f"slot{b}", "serve", t0, t - t0,
                                  uid=req.uid, tokens=len(req.out),
                                  reason=reason)
        self.completed[req.uid] = req
        self.slots[b] = None
        self.pos[b] = 0
        self.kv.release(b)

    def cancel(self, reqs):
        """Withdraw requests (queued, active, or completed) without
        completing them: queue entries are dropped, active slots are
        released, and their `completed` entries (matched by identity,
        not just uid) are removed.  Used by the facade to clean up
        abandoned streams."""
        targets = {id(r) for r in reqs}
        if not targets:
            return
        self.queue = deque(r for r in self.queue if id(r) not in targets)
        for b in range(self.max_batch):
            r = self.slots[b]
            if r is not None and id(r) in targets:
                self.slots[b] = None
                self.pos[b] = 0
                self.kv.release(b)
        for r in reqs:
            if self.completed.get(r.uid) is r:
                del self.completed[r.uid]
            self._req_meta.pop(id(r), None)

    def _grow_active(self, active: List[int], upto_fn) -> List[int]:
        """Paged growth with preemption-by-eviction, shared by decode
        and spec rounds: oldest-admitted slots grow first (never
        starved), `upto_fn(b)` gives each slot's target cache position,
        and a slot may evict itself as the last resort.  Returns the
        surviving active list."""
        for b in sorted(active, key=lambda b: self.admit_seq[b]):
            if self.slots[b] is None:   # preempted by an earlier slot
                continue
            while not self.kv.ensure(b, upto_fn(b)):
                v = self._preempt_one(keep=b)
                if v is None or v == b:
                    break
        return self._active()

    def _preempt_one(self, keep: int) -> Optional[int]:
        """Evict the latest-admitted active slot (other than `keep` when
        possible); its request requeues at the front with output kept."""
        cands = [b for b in range(self.max_batch)
                 if self.slots[b] is not None and b != keep]
        if not cands:
            cands = [keep] if self.slots[keep] is not None else []
        if not cands:
            return None
        v = max(cands, key=lambda b: self.admit_seq[b])
        req = self.slots[v]
        req.n_preempted += 1
        self.kv.release(v)
        self.slots[v] = None
        self.pos[v] = 0
        self.queue.appendleft(req)
        self.n_preemptions += 1
        self.obs.inc("preemptions_total")
        if self.obs.enabled:
            t = self.obs.now()
            self.obs.instant(f"slot{v}", "preempt", uid=req.uid,
                             n_preempted=req.n_preempted)
            meta = self._req_meta.get(id(req))
            if meta is not None:
                t0 = meta.get("serve_start", t)
                self.obs.complete(f"slot{v}", "serve", t0, t - t0,
                                  uid=req.uid, preempted=True)
                meta["submit"] = t       # queue wait restarts at requeue
        return v

    # ---------------- main loop ----------------

    def _active(self) -> List[int]:
        return [b for b in range(self.max_batch)
                if self.slots[b] is not None]

    def _decode_active(self, active: List[int]):
        """One decode step; greedy batches use the engines' fused greedy
        path (bit-identical to the pre-facade servers), anything else the
        sampled path with per-request SamplingParams arrays."""
        cur = jnp.asarray(self.cur)
        pos = jnp.asarray(self.pos)
        if all((self.slots[b].sampling or _GREEDY).greedy for b in active):
            return self.kv.decode(self.params, cur, pos)
        n = self.max_batch
        t = np.zeros(n, np.float32)
        k = np.zeros(n, np.int32)
        p = np.ones(n, np.float32)
        seeds = np.zeros(n, np.int32)
        counts = np.zeros(n, np.int32)
        for b in active:
            sp = self.slots[b].sampling or _GREEDY
            t[b], k[b], p[b] = sp.temperature, sp.top_k, sp.top_p
            seeds[b] = sp.seed
            counts[b] = len(self.slots[b].out)
        keys = RS.make_keys(seeds, counts)
        return self.kv.decode_sampled(self.params, cur, pos, t, k, p, keys)

    # ---------------- speculative decoding ----------------

    @property
    def spec_acceptance(self) -> float:
        """Fraction of drafted tokens the exact model accepted."""
        return self.spec_accepted / max(self.spec_drafted, 1)

    @property
    def spec_tokens_per_step(self) -> float:
        """Committed tokens per request per verify round (> 1.0 means
        speculation is paying for itself in decode steps)."""
        return self.spec_committed / max(self.spec_row_rounds, 1)

    def _spec_cap(self, b: int) -> int:
        """Cache positions request b may ever need — the bound its
        admission was validated against."""
        req = self.slots[b]
        return len(np.asarray(req.prompt)) + self._max_new(req)

    def _spec_round_k(self, active: List[int]) -> Dict[int, int]:
        """Per-row draft budget this round: fixed spec.k, or — adaptive
        mode — the slot's walked budget (grown on fully accepted rounds,
        shrunk after two consecutive zero-acceptance rounds; see
        `SpecConfig` and docs/speculative.md)."""
        if getattr(self.spec, "adaptive", False):
            return {b: int(self._spec_kb[b]) for b in active}
        return {b: self.spec.k for b in active}

    def _spec_adapt(self, b: int, k_b: int, n_acc: int, used_alt: int):
        """Walk slot b's budget from this round's outcome."""
        if n_acc >= k_b:
            self._spec_kb[b] = min(k_b + 1, self.spec.k_cap)
            self._spec_rej[b] = 0
        elif n_acc == 0 and not used_alt:
            self._spec_rej[b] += 1
            if self._spec_rej[b] >= 2:
                self._spec_kb[b] = max(self.spec.k_min, k_b - 1)
                self._spec_rej[b] = 0
        else:
            self._spec_rej[b] = 0

    def _spec_step(self, active: List[int]) -> bool:
        """One draft / verify-once round for every active slot.

        The round budget k is the max of the per-row budgets (fixed
        spec.k, or adaptive — `_spec_round_k`), so the verify forward
        compiles one shape per distinct k in [k_min, k_max]; a row with
        a smaller budget k_b clamps its acceptance to its own first k_b
        drafts (its surplus verify rows score positions that can never
        be committed — dense writes past the slot are dropped by the
        scatter, paged writes land in the trash page — so the surplus
        logits are garbage-but-discarded by construction, never acted
        on).  Rows whose remaining decode budget is tighter than k_b
        clamp their commits the same way.

        With tree_width w > 1 the chunk is [cur, d_1..d_k, a_1..a_
        {w-1}]: the draft's first-position runners-up verify as depth-1
        tree branches in the SAME forward (spec/verify.tree_layout), and
        a row whose first chain draft is rejected still commits two
        tokens when the target's correction matches an alternative —
        after relocating the alternative's KV from its chunk slot to the
        committed stream position (copy_pos, BEFORE rollback frees the
        chunk pages).

        Verify writes KV at positions pos..pos+C-1 (C = k + w), so
        paged slots must own pages through pos+C up front (same
        preemption-by-eviction rule as decode growth), capped at the
        request's validated capacity; after acceptance the rejected
        suffix rolls back — position rewind on dense, page truncation on
        paged (`PagePool.shrink`)."""
        adaptive = getattr(self.spec, "adaptive", False)
        w = getattr(self.spec, "tree_width", 1)
        kb = self._spec_round_k(active)
        k = max(kb.values())
        chunk = k + w                 # verify width: cur + chain + alts
        if self.kv.paged:
            active = self._grow_active(
                active,
                lambda b: min(int(self.pos[b]) + chunk,
                              self._spec_cap(b) - 1))
            if not active:
                return bool(self.queue)
        dr = self.spec.drafter
        n = self.max_batch
        # catch-up context: the committed tokens from each row's draft
        # coverage up to its current token (1 or 2 tokens — Drafter
        # invariant; re-processing a written position is idempotent)
        width = 1
        for b in active:
            width = max(width, int(self.pos[b]) - int(dr.pos[b]) + 1)
        all_greedy = all((self.slots[b].sampling or _GREEDY).greedy
                         for b in active)
        ctx = np.zeros((n, width), np.int32)
        start = np.zeros(n, np.int32)
        rngs: Dict[int, object] = {}
        alt_ok: Dict[int, bool] = {}
        for b in active:
            stream = self._resume_tokens(self.slots[b])
            p = int(self.pos[b])
            start[b] = p - width + 1
            ctx[b] = stream[start[b]: p + 1]
            sp = self.slots[b].sampling or _GREEDY
            rngs[b] = spec_rng(sp.seed, len(self.slots[b].out))
            # an alternative is only usable when its chunk slot
            # (pos+k+1..pos+C-1) really holds its KV — inside the dense
            # slot / the grown page coverage — and the row may still
            # commit two tokens; otherwise the row falls back to chain
            # acceptance (committing fewer tokens never changes the
            # greedy stream, so this guard preserves token identity)
            cap = (self._spec_cap(b) - 1 if self.kv.paged
                   else self.cache_len)
            alt_ok[b] = (w > 1 and p + chunk <= cap
                         and self._max_new(self.slots[b])
                         - len(self.slots[b].out) >= 2)
        if all_greedy:
            sampling = None
        else:
            # per-request SamplingParams arrays + per-draft-index keys
            # for the fused sampled draft (temp <= 0 rows draft greedy,
            # mirroring decode_sampled)
            t = np.zeros(n, np.float32)
            tk = np.zeros(n, np.int32)
            tp_ = np.ones(n, np.float32)
            seeds = np.zeros(n, np.int32)
            counts = np.zeros(n, np.int32)
            for b in active:
                sp = self.slots[b].sampling or _GREEDY
                t[b], tk[b], tp_[b] = sp.temperature, sp.top_k, sp.top_p
                seeds[b] = sp.seed
                counts[b] = len(self.slots[b].out)
            # draft draw i folds in a count disjoint from the committed-
            # token stream's fold_in counter (which is just len(out))
            keys = jnp.stack([RS.make_keys(seeds, counts * 131 + 17 + i)
                              for i in range(k)], axis=1)
            sampling = (t, tk, tp_, keys)
        with self.obs.span("spec", "draft", k=k, rows=len(active),
                           tree=w):
            draft_toks, draft_logits, alts = dr.draft(
                ctx, start, k, greedy=all_greedy,
                tree_width=w, sampling=sampling)
        ver = np.concatenate([self.cur, draft_toks], axis=1)   # (n, k+1)
        tree = None
        if w > 1:
            ver = np.concatenate(
                [ver, np.asarray(alts, np.int32)], axis=1)     # (n, k+w)
            tree = tree_layout(k, w)
        with self.obs.span("spec", "verify", rows=len(active), tree=w):
            lg = self.kv.verify(self.params, jnp.asarray(ver),
                                jnp.asarray(self.pos), tree=tree)
        if all_greedy:
            # mirror the fused-greedy decode path: only the (n, C)
            # argmax ids come to host, never the full-vocab logits
            argmax = np.asarray(jnp.argmax(lg, axis=-1))
            logits = None
        else:
            logits = np.asarray(lg)
            argmax = None
        self.spec_rounds += 1
        relocs: List[int] = []        # rows committing via an alt
        post = []                     # deferred rollback/finish work
        for b in active:
            req = self.slots[b]
            sp = req.sampling or _GREEDY
            k_b = kb[b]
            row_alts = alts[b] if alt_ok[b] else None
            if logits is None:
                committed, n_acc, used_alt = accept_greedy_tree(
                    draft_toks[b][:k_b], row_alts, argmax[b][:k_b + 1],
                    argmax[b][k + 1:])
            else:
                if sp.greedy:
                    dp = None
                else:
                    # reconstruct each draft draw's exact distribution q
                    # from the returned logits (filtered_probs mirrors
                    # the on-device sampling core's filtering)
                    dp = np.stack([
                        filtered_probs(draft_logits[b, i], sp.temperature,
                                       sp.top_k, sp.top_p)
                        for i in range(k_b)])
                committed, n_acc, used_alt = accept_speculative_tree(
                    draft_toks[b][:k_b], dp, logits[b][:k_b + 1],
                    row_alts, logits[b][k + 1:],
                    temperature=sp.temperature, top_k=sp.top_k,
                    top_p=sp.top_p, rng=rngs[b])
            old_pos = int(self.pos[b])
            req.n_drafted += k_b
            req.n_draft_accepted += n_acc
            self.spec_drafted += k_b
            self.spec_accepted += n_acc
            self.spec_row_rounds += 1
            if used_alt:
                self.spec_alt_commits += 1
            if self.obs.enabled:
                self.obs.inc("spec_drafted_total", k_b)
                self.obs.inc("spec_accepted_total", n_acc)
                if used_alt:
                    self.obs.inc("spec_tree_alt_commits_total")
                if adaptive:
                    self.obs.gauge("spec_k", k_b, slot=str(b))
                self.obs.metrics.observe("spec_acceptance_ratio",
                                         n_acc / k_b,
                                         buckets=_ACCEPT_BUCKETS)
            if adaptive:
                self._spec_adapt(b, k_b, n_acc, used_alt)
            budget = self._max_new(req) - len(req.out)
            done_b = False
            for tok in committed[:budget]:
                req.out.append(tok)
                self.spec_committed += 1
                self.pos[b] += 1
                self.cur[b, 0] = tok
                if self._stopping(req, tok):
                    done_b = True
                    break
            # the alt's KV needs relocating only if the row keeps
            # generating (a finishing row's slot is released whole)
            if used_alt and not done_b:
                relocs.append((b, old_pos + k + used_alt, old_pos + 1))
            post.append((b, done_b, used_alt, old_pos))
        if relocs:
            # relocate BEFORE any rollback below: truncate/shrink frees
            # the pages holding the chunk slots the alts live in
            src = np.zeros(n, np.int32)
            dst = np.zeros(n, np.int32)
            for b, s_, d_ in relocs:
                src[b], dst[b] = s_, d_
            self.kv.copy_pos(src, dst)
        for b, done_b, used_alt, old_pos in post:
            if done_b:
                self._finish(b)
                continue
            self.kv.truncate(b, int(self.pos[b]))
            if used_alt:
                # the draft cache's position old_pos+1 holds the CHAIN
                # draft's KV, not the committed alternative's — next
                # round's catch-up context rewrites it
                dr.pos[b] = old_pos + 1
            else:
                # draft cache validity: it wrote positions old_pos..
                # old_pos+k-1 for [cur, d_1..d_{k-1}]; the accepted
                # prefix keeps it in sync up to min(committed end,
                # old_pos + k)
                dr.pos[b] = min(int(self.pos[b]), old_pos + k)
        return True

    # ---------------- main loop (continued) ----------------

    def step(self) -> bool:
        """Admit, grow (paged), one decode step for all active slots.
        With speculation enabled the decode step becomes a draft/verify
        round that can commit up to k+1 tokens per request."""
        if not self.obs.enabled:
            return self._step()
        with self.obs.span("scheduler", "step") as s:
            out = self._step()
            act = len(self._active())
            s["active"] = act
            s["queued"] = len(self.queue)
            self.obs.gauge("active_slots", act)
            self.obs.gauge("queue_depth", len(self.queue))
            self.obs.counter_event("scheduler", "active_slots", act)
        return out

    def _step(self) -> bool:
        self._admit()
        active = self._active()
        if not active:
            # every admitted request may have finished on its admission
            # token; requests still queued need another step
            return bool(self.queue)
        if self.spec is not None:
            return self._spec_step(active)
        # host phases of a decode step, as spans: `prep` (growth, input
        # uploads, COW barrier, page table, dispatch), `wait` (blocked on
        # the device, then the token copy), `commit` (appends, stops)
        with self.obs.span("scheduler", "prep"):
            if self.kv.paged:
                # growth: each slot writes position pos[b] this step —
                # make sure its page exists (preemption: _grow_active)
                active = self._grow_active(active,
                                           lambda b: int(self.pos[b]) + 1)
                if not active:
                    return bool(self.queue)
            nxt = self._decode_active(active)
        with self.obs.span("scheduler", "wait"):
            nxt = np.asarray(nxt)
        with self.obs.span("scheduler", "commit"):
            for b in active:
                req = self.slots[b]
                tok = int(nxt[b, 0])
                req.out.append(tok)
                self.pos[b] += 1
                self.cur[b, 0] = tok
                if self._stopping(req, tok):
                    self._finish(b)
        return True

    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def outstanding_tokens(self) -> int:
        """Token-work backlog of this scheduler: queued requests count
        their full prefill (prompt + kept output) plus remaining decode
        budget, active slots their remaining decode budget.  This is the
        load signal the cluster router's least-outstanding-tokens policy
        balances on (docs/cluster.md)."""
        n = 0
        for r in self.queue:
            n += len(r.prompt) + len(r.out) + (self._max_new(r)
                                               - len(r.out))
        for b in self._active():
            r = self.slots[b]
            n += self._max_new(r) - len(r.out)
        return n

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        steps = 0
        while self.has_work() and steps < max_steps:
            if not self.step():
                break
            steps += 1
        return self.completed
