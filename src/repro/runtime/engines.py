"""The serving engine: ONE `Engine` over pluggable parallel backends.

Historically this module carried two mirrored engines — `SimEngine`
(vmap simulated TP) and `ShardEngine` (shard_map over a device mesh) —
each re-implementing every forward step.  The forward math now lives
once in `repro.runtime.forward` (backend-agnostic local functions) and
`repro.parallel.backend.ParallelBackend` owns the lift: `Engine(cfg,
plan, backend)` compiles each step lazily through `backend.wrap` and
keeps caches in the backend's native layout between calls.

    prefill(params, tokens, *, cache_len, lengths) -> (full logits, caches1)
    prefill_chunked(...)  — incremental prefill in fixed-size chunks
    decode / decode_with_logits / decode_sampled       dense decode
    decode_paged / decode_paged_with_logits / decode_paged_sampled
    verify / verify_paged                 multi-token speculative verify
    blank_caches / blank_paged_caches, insert_slot / insert_paged

`SimEngine(cfg, plan, tp)` and `ShardEngine(cfg, plan, mesh)` remain as
thin constructors over the registered backends, so pre-unification call
sites keep working; new code should resolve backends by registry name
(`repro.parallel.backend.make_backend`, or `LLM.load(engine=...)`).

Comm policy: a plan with an attached CommPolicy (plan.comm — see
docs/comm.md) changes what the compiled steps emit per block; engine,
param placement, and cache trees must all be built from the SAME plan
object — `repro.api.LLM` guarantees this.  KV caches are donated on
every decode/verify step (runtime/forward.py documents the contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, SPDPlanConfig
from repro.core import model as M
from repro.parallel.backend import ParallelBackend, make_backend
from repro.runtime import forward as F
from repro.runtime.forward import bucketed_prefill  # re-export  # noqa: F401

__all__ = ["Engine", "SimEngine", "ShardEngine", "bucketed_prefill"]


class Engine:
    """One serving engine over a `ParallelBackend` (see module doc)."""

    def __init__(self, cfg: ModelConfig, plan: SPDPlanConfig,
                 backend: ParallelBackend, q_chunk: int = 1024):
        self.cfg, self.plan, self.backend = cfg, plan, backend
        self.q_chunk = q_chunk
        self.tp = backend.tp
        self.mesh = getattr(backend, "mesh", None)
        self._steps = {}

    def _step(self, key, builder):
        """The jitted step under `key`, built on first use.  Its module
        is named after the key (`jit_decode_paged`, `jit_prefill_chunk`,
        ...), so a profiler trace names each program by what it is."""
        if key not in self._steps:
            local_fn, spec = builder()
            local_fn.__name__ = local_fn.__qualname__ = key[0]
            self._steps[key] = self.backend.wrap(local_fn, spec)
        return self._steps[key]

    # ---- cache trees (backend-native layout) ----

    def blank_caches(self, batch: int, cache_len: int, replicated=False):
        structs = M.cache_struct(self.cfg, self.plan, batch, cache_len,
                                 self.tp)
        return self.backend.blank_caches(structs,
                                         shard_batch=not replicated)

    def blank_paged_caches(self, max_slots: int, cache_len: int, *,
                           page_size: int, num_pages: int):
        structs = M.paged_cache_struct(
            self.cfg, self.plan, max_slots, cache_len, self.tp,
            page_size=page_size, num_pages=num_pages)
        return self.backend.blank_caches(structs, shard_batch=False)

    def insert_slot(self, caches, caches1, b: int):
        return F.insert_slot(caches, caches1, b,
                             batch_axis=self.backend.cache_batch_axis)

    def insert_paged(self, pcaches, caches1, b: int, page_row):
        step = self._step(("insert_paged",),
                          lambda: F.insert_paged_step(self.cfg, self.plan))
        return step(pcaches, caches1, jnp.int32(b),
                    jnp.asarray(page_row, jnp.int32))[0]

    def copy_paged_pages(self, pcaches, src, dst):
        """COW page duplication: copy physical page src[i] -> dst[i] on
        every pageable leaf (runtime/paging.py ensure_writable decides
        the pairs; the pool rewires the slot's table host-side)."""
        step = self._step(("copy_pages", len(src)),
                          lambda: F.copy_pages_step(self.cfg, self.plan))
        return step(pcaches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))[0]

    # ---- compiled forward steps ----

    def prefill(self, params, tokens, *, cache_len: int, lengths=None,
                embeds=None):
        # pad the request batch to a multiple of the data axes (single
        # requests on a dp>1 mesh); slice the result back out after
        dpn = self.backend.dp_total
        b0 = tokens.shape[0]
        pad = (-b0) % dpn
        if pad:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((pad,) + tokens.shape[1:], tokens.dtype)])
            if lengths is not None:
                lengths = jnp.concatenate(
                    [lengths, jnp.ones((pad,), lengths.dtype)])
            if embeds is not None:
                embeds = jnp.concatenate(
                    [embeds, jnp.zeros((pad,) + embeds.shape[1:],
                                       embeds.dtype)])
        key = ("prefill", tokens.shape, cache_len, embeds is not None)
        step = self._step(key, lambda: F.prefill_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk,
            cache_len=cache_len))
        lg, caches = step(params, tokens, lengths, embeds)
        if pad:
            lg = lg[:b0]
            pre = (slice(None),) * self.backend.cache_batch_axis
            caches = jax.tree.map(lambda c: c[pre + (slice(None, b0),)],
                                  caches)
        return lg, caches

    def prefill_chunked(self, params, tokens, *, cache_len: int, lengths,
                        chunk: int):
        """Incremental prefill in fixed-size chunks.

        Compilation is keyed on (chunk, cache_len) only, so prompt-length
        variation costs zero recompiles (vs per-bucket specialization at
        power-of-two lengths).  tokens (B, S) right-padded; lengths (B,)
        real lengths — chunks past max(lengths) are skipped.  Falls back
        to one-shot prefill for archs without chunked support."""
        if not M.supports_chunked_prefill(self.cfg):
            return self.prefill(params, tokens, cache_len=cache_len,
                                lengths=jnp.asarray(lengths, jnp.int32))
        key = ("prefill_chunk", int(chunk), cache_len)
        step = self._step(key, lambda: F.prefill_chunk_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk))
        return F.drive_chunked_prefill(
            lambda t, st, ln, cs: step(params, t, st, ln, cs),
            self.blank_caches(tokens.shape[0], cache_len, replicated=True),
            tokens, lengths, chunk)

    def _decode(self, with_logits: bool):
        return self._step(("decode", with_logits), lambda: F.decode_step(
            self.cfg, self.plan, tp=self.tp, with_logits=with_logits))

    def decode(self, params, tokens, pos, caches):
        return self._decode(False)(params, tokens, pos, caches)

    def decode_with_logits(self, params, tokens, pos, caches):
        return self._decode(True)(params, tokens, pos, caches)

    def decode_sampled(self, params, tokens, pos, caches, temperature,
                       top_k, top_p, keys):
        """Decode with the jitted sampling step fused in (per-request
        temperature / top-k / top-p / key; temp <= 0 rows are greedy)."""
        step = self._step(("decode_sampled",), lambda: F.decode_step(
            self.cfg, self.plan, tp=self.tp, sampled=True))
        return step(params, tokens, pos, caches, temperature, top_k,
                    top_p, keys)

    def decode_pipelined(self, params, groups, *, depth: int = 2):
        """Greedy decode over independent micro-batches with async
        dispatch between them (F.drive_pipelined_decode) — the host-level
        overlap seam the "overlap" backend pairs with its chunked-ring
        sync accounting.  `groups` is a list of ``(tokens, pos, caches)``;
        returns ``[(ids, caches), ...]`` token-identical to calling
        `decode` serially per group (any backend; scheduler batches that
        split along request groups can use it directly)."""
        return F.drive_pipelined_decode(self._decode(False), params,
                                        groups, depth=depth)

    def verify(self, params, tokens, pos, caches, tree=None):
        """Speculative verify on dense caches: tokens (B, C) — the last
        accepted token + C-1 drafts — scored in ONE forward; returns
        (full logits (B, C, V), new caches).  See M.verify_step for the
        per-row position + rollback contract.  `tree=(depths, anc)` —
        static tuples from spec/verify.tree_layout — verifies a draft
        TREE chunk (chain + alternative branches) instead of a chain."""
        step = self._step(("verify", tokens.shape, tree),
                          lambda: F.verify_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk,
            tree=tree))
        return step(params, tokens, pos, caches)

    def verify_paged(self, params, tokens, pos, page_table, pcaches,
                     tree=None):
        """Paged speculative verify: gather pages -> dense verify math ->
        scatter every newly written token back into its page.  `tree` as
        in `verify` (tree chunks scatter contiguously, so paged rollback
        is identical to chains)."""
        key = ("verify_paged", tokens.shape, tree)
        step = self._step(key, lambda: F.paged_verify_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk,
            n_tokens=int(tokens.shape[1]), tree=tree))
        return step(params, tokens, pos, page_table, pcaches)

    # ---- fused self-draft steps (spec/draft.py Drafter) ----

    def draft(self, params, ctx, start, caches, *, k: int):
        """Fused greedy k-token self-draft: catch-up verify + a scanned
        k-1 decode chain in ONE jitted dispatch (F.draft_step).  Returns
        (draft tokens (B, k) int32, new caches); caches donated."""
        key = ("draft", ctx.shape, int(k))
        step = self._step(key, lambda: F.draft_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk, k=k))
        return step(params, ctx, start, caches)

    def draft_tree(self, params, ctx, start, caches, *, k: int,
                   width: int):
        """Fused greedy draft that also surfaces the first position's
        top-2..top-`width` candidates as tree alternatives.  Returns
        (toks (B, k), alts (B, width-1), caches)."""
        key = ("draft_tree", ctx.shape, int(k), int(width))
        step = self._step(key, lambda: F.draft_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk, k=k,
            tree_width=width))
        return step(params, ctx, start, caches)

    def draft_sampled(self, params, ctx, start, caches, temperature,
                      top_k, top_p, keys, *, k: int):
        """Fused sampled draft: per-request temperature / top-k / top-p
        and per-draft-index keys (B, k, 2) drive the shared jitted
        sampling core inside the scan.  Returns (toks (B, k), full
        logits (B, k, V), caches) — the logits become the rejection
        scheme's q distributions host-side."""
        key = ("draft_sampled", ctx.shape, int(k))
        step = self._step(key, lambda: F.draft_step(
            self.cfg, self.plan, tp=self.tp, q_chunk=self.q_chunk, k=k,
            sampled=True))
        return step(params, ctx, start, caches, temperature, top_k,
                    top_p, keys)

    def copy_pos(self, caches, src, dst):
        """Per-row cache position copy src[b] -> dst[b] on dense caches
        (tree speculation relocates an accepted alternative branch's KV
        to its true stream position; src == dst rows are no-ops)."""
        step = self._step(("copy_pos",),
                          lambda: F.copy_pos_step(self.cfg, self.plan))
        return step(caches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))[0]

    def copy_pos_paged(self, pcaches, page_table, src, dst, *,
                       page_size: int):
        """copy_pos through the page table (unallocated pages resolve to
        the trash page, so padded rows are harmless)."""
        step = self._step(("copy_pos_paged", int(page_size)),
                          lambda: F.copy_pos_paged_step(
            self.cfg, self.plan, page_size=page_size))
        return step(pcaches, page_table, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))[0]

    def _decode_paged(self, with_logits: bool):
        return self._step(
            ("decode_paged", with_logits),
            lambda: F.paged_decode_step(self.cfg, self.plan, tp=self.tp,
                                        with_logits=with_logits))

    def decode_paged(self, params, tokens, pos, page_table, pcaches):
        return self._decode_paged(False)(params, tokens, pos,
                                         page_table, pcaches)

    def decode_paged_with_logits(self, params, tokens, pos, page_table,
                                 pcaches):
        return self._decode_paged(True)(params, tokens, pos,
                                        page_table, pcaches)

    def decode_paged_sampled(self, params, tokens, pos, page_table, pcaches,
                             temperature, top_k, top_p, keys):
        """Paged decode with the jitted sampling step fused in."""
        step = self._step(
            ("decode_paged_sampled",),
            lambda: F.paged_decode_step(self.cfg, self.plan, tp=self.tp,
                                        sampled=True))
        return step(params, tokens, pos, page_table, pcaches,
                    temperature, top_k, top_p, keys)


def SimEngine(cfg: ModelConfig, plan: SPDPlanConfig, tp: int,
              q_chunk: int = 1024) -> Engine:
    """Simulated-TP engine (vmap, 1 CPU device) — thin constructor over
    the registered "sim" backend."""
    return Engine(cfg, plan, make_backend("sim", cfg, plan, tp=tp),
                  q_chunk=q_chunk)


def ShardEngine(cfg: ModelConfig, plan: SPDPlanConfig, mesh,
                q_chunk: int = 1024) -> Engine:
    """Real-TP engine (shard_map over `mesh`) — thin constructor over
    the registered "shard" backend."""
    return Engine(cfg, plan, make_backend("shard", cfg, plan, mesh=mesh),
                  q_chunk=q_chunk)
