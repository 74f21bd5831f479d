"""Paged KV-cache runtime: allocator invariants (grow/release/shrink),
copy-on-write sharing + prefix cache, a hypothesis property soak over
the allocator, paged-vs-dense decode equivalence on every registry
backend, chunked prefill, and a preemption soak."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                       # property soak skips, the
    hypothesis = None                     # deterministic tests still run

    def _skip_deco(*a, **k):
        def deco(f):
            return pytest.mark.skip(reason="hypothesis not installed")(f)
        return deco

    given = settings = _skip_deco

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

from conftest import engine_for_backend, make_cfg
from repro.api.scheduler import CacheConfig, Request, Scheduler
from repro.config.base import SPDPlanConfig
from repro.core import model as M, simtp
from repro.parallel.backend import backend_names
from repro.runtime.engines import SimEngine
from repro.runtime.paging import PagePool, page_hashes

EXAMPLES = int(os.environ.get("SOAK_EXAMPLES", "25"))


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_pool_alloc_free_invariants():
    pool = PagePool(num_pages=8, page_size=4, max_slots=3, pages_per_slot=4)
    pool.check()
    assert pool.pages_for(0) == 0 and pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1 and pool.pages_for(5) == 2
    assert pool.grow(0, 9)            # 3 pages
    assert pool.num_free == 5
    assert pool.grow(0, 9)            # idempotent
    assert pool.num_free == 5
    assert pool.grow(1, 16)           # 4 pages
    pool.check()
    assert pool.num_free == 1
    assert not pool.grow(2, 8)        # needs 2, only 1 free: all-or-nothing
    assert pool.num_free == 1 and pool.owned[2] == 0
    pool.check()
    assert pool.release(1) == 4
    assert pool.grow(2, 8)
    pool.check()
    # per-slot cap: pages_per_slot bounds growth even with free pages
    assert not pool.grow(2, 17)
    pool.reset()
    pool.check()
    assert pool.num_free == 8


def test_pool_shrink_truncates_and_returns_pages():
    """Speculative rollback: shrink returns exactly the suffix pages to
    the free list and preserves the table's valid-prefix invariant."""
    pool = PagePool(num_pages=8, page_size=4, max_slots=2, pages_per_slot=4)
    assert pool.grow(0, 16)               # 4 pages
    kept = [int(p) for p in pool.table[0][:2]]
    assert pool.shrink(0, 7) == 2         # 7 tokens -> 2 pages
    pool.check()
    assert int(pool.owned[0]) == 2
    assert [int(p) for p in pool.table[0][:2]] == kept   # prefix untouched
    assert (pool.table[0][2:] == -1).all()
    assert pool.num_free == 6
    # no-ops: shrink to >= current allocation, or on an empty slot
    assert pool.shrink(0, 8) == 0 and pool.shrink(0, 100) == 0
    assert pool.shrink(1, 0) == 0
    pool.check()
    # shrink to zero tokens == release
    assert pool.shrink(0, 0) == 2
    assert pool.num_free == 8
    pool.check()
    # released pages are immediately reusable by another slot
    assert pool.grow(1, 16)
    pool.check()


def test_pool_fits_alone():
    pool = PagePool(num_pages=4, page_size=8, max_slots=2, pages_per_slot=8)
    assert pool.fits_alone(32)
    assert not pool.fits_alone(33)    # 5 pages > pool
    pool2 = PagePool(num_pages=16, page_size=8, max_slots=2,
                     pages_per_slot=2)
    assert not pool2.fits_alone(17)   # 3 pages > per-slot table width


# ---------------------------------------------------------------------------
# Prefix cache + copy-on-write (allocator level)
# ---------------------------------------------------------------------------


def test_pool_reset_canonical():
    """reset() restores the EXACT fresh-pool state — free-list order
    included — regardless of the alloc/release history that preceded it,
    so physical page assignment is reproducible across runs (this test
    locks the free-list nondeterminism fix)."""
    fresh = PagePool(num_pages=8, page_size=4, max_slots=3,
                     pages_per_slot=4)
    pool = PagePool(num_pages=8, page_size=4, max_slots=3,
                    pages_per_slot=4)
    # scramble: interleaved grows/shrinks/releases + prefix registration
    assert pool.grow(1, 13) and pool.grow(0, 9) and pool.grow(2, 4)
    pool.register_prefix(1, np.arange(12))
    pool.shrink(0, 2)
    pool.release(1)                       # registered pages -> cached LRU
    assert pool.grow(1, 5)
    pool.release(0), pool.release(2), pool.release(1)
    assert pool.free != fresh.free        # history really did reorder it
    pool.reset()
    assert pool.free == fresh.free
    assert (pool.table == fresh.table).all()
    assert (pool.refs == fresh.refs).all() and (pool.owned == 0).all()
    assert not pool.cached and not pool.page_hash and not pool.prefix_index
    pool.check()


def test_prefix_register_match_share():
    pool = PagePool(num_pages=8, page_size=4, max_slots=3, pages_per_slot=4)
    toks = np.arange(10, dtype=np.int64)          # 2 full pages + partial
    assert pool.grow(0, 10)
    pool.register_prefix(0, toks)
    assert len(pool.page_hash) == 2               # partial page not hashed
    assert len(page_hashes(toks, 4)) == 2
    m = pool.match_prefix(toks)
    assert m == [int(pool.table[0, 0]), int(pool.table[0, 1])]
    # a prompt diverging in page 2 matches only page 1
    other = toks.copy()
    other[5] += 1
    assert pool.match_prefix(other) == m[:1]
    assert pool.match_prefix(toks[:4]) == m[:1]   # only 1 full page given
    assert pool.match_prefix(toks[:3]) == []
    # share into an empty slot: refcounts, not copies
    pool.share_prefix(1, m)
    assert int(pool.refs[m[0]]) == 2 and int(pool.owned[1]) == 2
    pool.check()
    # registration is idempotent and keeps the index bijective even when
    # a second slot re-registers the same (shared) content
    pool.register_prefix(1, toks)
    assert len(pool.page_hash) == 2
    pool.check()
    # releasing both references parks the pages in the cached LRU: still
    # matchable, still counted as allocatable
    pool.release(0), pool.release(1)
    assert (pool.refs == 0).all()
    assert pool.num_free == 8 and len(pool.cached) == 2
    assert pool.match_prefix(toks) == m
    pool.check()


def test_page_hashes_one_pass_chain():
    """The vectorized hasher's prefix property must hold (the capped
    admission match reuses a slice of the full-prompt digests), and the
    precomputed-hashes fast paths of match/register must be
    indistinguishable from hashing in place.  The reference
    `page_hashes_chain` must equal the definitional blake2b chain."""
    import hashlib
    from repro.runtime.paging import page_hashes_chain
    toks = np.arange(23, dtype=np.int64)
    got = page_hashes(toks, 4)
    assert len(got) == 5                          # 23 // 4 full pages
    assert len(set(got)) == 5 and all(len(h) == 16 for h in got)
    h = b""
    for j, ref in enumerate(page_hashes_chain(toks, 4)):
        h = hashlib.blake2b(
            h + toks[4 * j:4 * (j + 1)].tobytes(), digest_size=16).digest()
        assert ref == h
    # chain-prefix property: digests of a capped prompt are a prefix of
    # the full prompt's digests (hash once per admission relies on this)
    assert page_hashes(toks[:12], 4) == got[:3]
    assert page_hashes(toks[:3], 4) == []

    pool = PagePool(num_pages=8, page_size=4, max_slots=2, pages_per_slot=4)
    assert pool.grow(0, 16)
    pool.register_prefix(0, toks[:16], hashes=page_hashes(toks[:16], 4))
    m = pool.match_prefix(toks)                   # hashed in place
    assert m == pool.match_prefix(None, hashes=got)   # precomputed
    assert len(m) == 4
    pool.check()


def test_page_hashes_equality_semantics_locked_to_chain():
    """The vectorized hasher must induce the SAME equality relation as
    the blake2b chain oracle: equal prefixes -> equal digests, and a
    divergence at page j breaks digests j onward.  Randomized trials
    compare the per-page equality pattern of (original, mutated) prompt
    pairs under both hashers — the only property the prefix index and
    prefix-affinity routing consume."""
    from repro.runtime.paging import page_hashes_chain
    rng = np.random.default_rng(7)
    for trial in range(120):
        ps = int(rng.integers(1, 9))
        n = int(rng.integers(0, 6))
        extra = int(rng.integers(0, ps))
        a = rng.integers(0, 50_000, n * ps + extra).astype(np.int32)
        b = a.copy()
        if n and rng.random() < 0.7:
            j = int(rng.integers(0, n * ps))
            b[j] = (b[j] + 1 + int(rng.integers(0, 100))) % 50_000
        ha, hb = page_hashes(a, ps), page_hashes(b, ps)
        ca, cb = page_hashes_chain(a, ps), page_hashes_chain(b, ps)
        assert len(ha) == len(ca) == n
        assert ([x == y for x, y in zip(ha, hb)]
                == [x == y for x, y in zip(ca, cb)]), (trial, ps)
    # order sensitivity: swapping two whole pages changes the digest of
    # every prefix that covers both (position-keyed weights, not a bag)
    t = rng.integers(0, 32_000, 8 * 16).astype(np.int32)
    u = t.copy()
    u[0:16], u[16:32] = t[16:32].copy(), t[0:16].copy()
    assert page_hashes(t, 16)[1:] != page_hashes(u, 16)[1:]
    # a prefix and its zero-extension never collide (boundary re-mix
    # folds the prefix length in)
    z = np.zeros(3 * 4, np.int32)
    assert len(set(page_hashes(z, 4))) == 3
    # odd weights: any single-token delta flips the covering digest
    # deterministically, exercised across the weight-cache growth path
    big = rng.integers(0, 32_000, 10_000).astype(np.int32)
    mut = big.copy()
    mut[9_990] += 2
    assert page_hashes(big, 16)[-1] != page_hashes(mut, 16)[-1]


def test_admission_hashes_prompt_once():
    """PagedKVCacheManager computes a prompt's chain digests once per
    admission (match + register reuse them) and never leaves stale
    digests behind for the slot."""
    from test_scheduler_soak import FakeEngine
    from repro.api.scheduler import CacheConfig, Request, Scheduler

    sched = Scheduler(FakeEngine(), None,
                      CacheConfig(cache_len=32, max_batch=2, page_size=4,
                                  num_pages=12, prefix_cache=True))
    p = np.arange(10, dtype=np.int32)
    sched.submit(Request(uid=0, prompt=p, max_new=2))
    sched.run()
    assert sched.kv._admit_hashes == {}           # consumed, not leaked
    # registered digests equal the batch hasher's output
    assert set(page_hashes(p, 4)) == set(sched.pool.prefix_index)
    # a second identical prompt admits through the prefix cache
    sched.submit(Request(uid=1, prompt=p.copy(), max_new=2))
    sched.run()
    assert sched.kv.prefix_hits == 1
    assert sched.kv._admit_hashes == {}


def test_cow_semantics():
    pool = PagePool(num_pages=6, page_size=4, max_slots=2, pages_per_slot=3)
    toks = np.arange(8, dtype=np.int64)
    assert pool.grow(0, 8)
    pool.register_prefix(0, toks)
    m = pool.match_prefix(toks)
    pool.share_prefix(1, m)
    # write to a shared page -> private copy + rewire + (src, dst) pair
    pair = pool.ensure_writable(1, 0)
    assert pair is not None and pair[0] == m[0]
    src, dst = pair
    assert int(pool.table[1, 0]) == dst != src
    assert int(pool.refs[src]) == 1 and int(pool.refs[dst]) == 1
    assert int(pool.table[0, 0]) == src           # slot 0 untouched
    pool.check()
    # write to a privately-owned but REGISTERED page -> deregister only
    pool.release(1)
    assert pool.ensure_writable(0, 1) is None
    assert len(pool.page_hash) == 1               # m[1]'s digest dropped
    pool.check()
    # already-private unregistered page -> plain no-op
    assert pool.ensure_writable(0, 1) is None
    pool.check()


def test_cow_pool_exhausted_raises():
    pool = PagePool(num_pages=2, page_size=4, max_slots=2, pages_per_slot=2)
    assert pool.grow(0, 8)
    pool.register_prefix(0, np.arange(8))
    pool.release(0)
    pool.share_prefix(0, pool.match_prefix(np.arange(8)))
    pool.share_prefix(1, pool.match_prefix(np.arange(8)))
    with pytest.raises(RuntimeError):
        pool.ensure_writable(1, 0)        # refs == 2, zero spare pages
    pool.check()


def test_prefix_cache_lru_eviction():
    """Cached (released-but-registered) pages are reclaimed least-
    recently-released first when the free list runs dry, and eviction
    deregisters them."""
    pool = PagePool(num_pages=4, page_size=2, max_slots=2, pages_per_slot=4)
    a, b = np.asarray([1, 2, 3, 4]), np.asarray([9, 8, 7, 6])
    assert pool.grow(0, 4) and pool.grow(1, 4)
    pool.register_prefix(0, a)
    pool.register_prefix(1, b)
    pool.release(0)                       # a's pages: oldest cached
    pool.release(1)
    assert len(pool.cached) == 4 and not pool.free
    # two pages re-allocated -> a's pages (LRU) evicted + deregistered
    assert pool.grow(0, 4)
    assert pool.match_prefix(a) == []
    assert len(pool.match_prefix(b)) == 2
    pool.check()


def _run_pool_soak(ints, choose):
    """One episode of random interleaved grow / shrink / release /
    register / share / COW: every allocator invariant must hold after
    every op (check()), no page may leak or be double-owned, and a full
    release must return every refcount to zero with the whole pool
    allocatable again.  `ints(lo, hi)` / `choose(options)` supply the
    randomness (a hypothesis draw or a seeded Generator)."""
    ps = choose([2, 4])
    pool = PagePool(num_pages=ints(4, 12), page_size=ps, max_slots=3,
                    pages_per_slot=ints(2, 6))
    cap = pool.pages_per_slot * ps
    seq = {s: [] for s in range(pool.max_slots)}   # committed tokens

    for _ in range(ints(5, 30)):
        op = choose(["grow", "grow", "shrink", "release", "register",
                     "share", "cow"])
        s = ints(0, pool.max_slots - 1)
        if op == "grow":
            t = ints(0, cap + ps)
            before = pool.num_free
            ok = pool.grow(s, t)
            if not ok:     # all-or-nothing: feasibility exactly predicted
                assert pool.pages_for(t) > pool.pages_per_slot \
                    or pool.pages_for(t) - int(pool.owned[s]) > before
            elif t > len(seq[s]):
                seq[s] += [ints(0, 9) for _ in range(t - len(seq[s]))]
        elif op == "shrink":
            t = ints(0, cap)
            pool.shrink(s, t)
            seq[s] = seq[s][:t]           # rollback commits only t tokens
        elif op == "release":
            pool.release(s)
            seq[s] = []
        elif op == "register":
            pool.register_prefix(s, np.asarray(seq[s], np.int64))
        elif op == "share":
            if int(pool.owned[s]) == 0:
                donor = ints(0, pool.max_slots - 1)
                m = pool.match_prefix(np.asarray(seq[donor], np.int64))
                m = m[:pool.pages_per_slot]
                pool.share_prefix(s, m)
                seq[s] = seq[donor][:len(m) * ps]
        elif op == "cow":
            own = int(pool.owned[s])
            if own:
                idx = ints(0, own - 1)
                try:
                    pool.ensure_writable(s, idx)
                except RuntimeError:
                    assert pool.num_free == 0
                else:
                    # content of page idx changes: divergent suffix
                    seq[s] = seq[s][:idx * ps]
        pool.check()
        for b in range(pool.max_slots):
            assert int(pool.owned[b]) <= pool.pages_per_slot

    for s in range(pool.max_slots):
        pool.release(s)
    pool.check()
    assert (pool.refs == 0).all()
    assert (pool.owned == 0).all() and (pool.table == -1).all()
    assert pool.num_free == pool.num_pages         # nothing leaked
    # reset from any end state == a fresh pool (determinism lock)
    pool.reset()
    fresh = PagePool(num_pages=pool.num_pages, page_size=ps,
                     max_slots=3, pages_per_slot=pool.pages_per_slot)
    assert pool.free == fresh.free and not pool.cached


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.data())
def test_pool_property_soak(data):
    _run_pool_soak(
        lambda lo, hi: data.draw(st.integers(lo, hi)),
        lambda opts: data.draw(st.sampled_from(opts)))


def test_pool_random_ops_seeded():
    """Deterministic rendition of the property soak, so the allocator
    invariants are exercised even where hypothesis is absent."""
    rng = np.random.default_rng(0)
    for _ in range(20):
        _run_pool_soak(lambda lo, hi: int(rng.integers(lo, hi + 1)),
                       lambda opts: opts[int(rng.integers(len(opts)))])


# ---------------------------------------------------------------------------
# Paged == dense decode (logits allclose), both engines
# ---------------------------------------------------------------------------

CACHE, PS, NPG = 64, 16, 10


def _prompts(cfg, lens=(12, 5, 27), seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _drive_equiv(engine, params, cfg, n_slots, steps=3):
    """Prefill 3 prompts into dense + paged caches, then co-decode and
    compare next tokens and full logits each step."""
    prompts = _prompts(cfg)
    dense = engine.blank_caches(n_slots, CACHE)
    pool = PagePool(num_pages=NPG, page_size=PS, max_slots=n_slots,
                    pages_per_slot=CACHE // PS)
    pc = engine.blank_paged_caches(n_slots, CACHE, page_size=PS,
                                   num_pages=NPG)
    pos = np.zeros(n_slots, np.int32)
    cur = np.zeros((n_slots, 1), np.int32)
    for b, p in enumerate(prompts):
        s = len(p)
        toks = np.zeros((1, 32), np.int32)
        toks[0, :s] = p
        lg, c1 = engine.prefill(params, jnp.asarray(toks), cache_len=CACHE,
                                lengths=jnp.asarray([s], jnp.int32))
        dense = engine.insert_slot(dense, c1, b)
        assert pool.grow(b, s + 1)
        pc = engine.insert_paged(pc, c1, b, pool.table[b])
        pos[b] = s
        cur[b, 0] = int(np.argmax(np.asarray(lg)[0]))
    nb = len(prompts)
    for _ in range(steps):
        for b in range(nb):
            assert pool.grow(b, int(pos[b]) + 1)
        n1, l1, dense = engine.decode_with_logits(
            params, jnp.asarray(cur), jnp.asarray(pos), dense)
        n2, l2, pc = engine.decode_paged_with_logits(
            params, jnp.asarray(cur), jnp.asarray(pos),
            jnp.asarray(pool.table), pc)
        np.testing.assert_array_equal(np.asarray(n1)[:nb],
                                      np.asarray(n2)[:nb])
        np.testing.assert_allclose(np.asarray(l1)[:nb], np.asarray(l2)[:nb],
                                   atol=2e-4, rtol=2e-4)
        pos[:nb] += 1
        cur = np.asarray(n1)
    pool.check()


@pytest.mark.parametrize("backend_name", backend_names())
@pytest.mark.parametrize("spd", [0, 2])
def test_paged_equals_dense(spd, backend_name):
    """Paged == dense decode logits, registry-generated backend axis."""
    cfg = make_cfg("smollm-360m")
    plan = SPDPlanConfig.first_k(cfg.n_layers, spd)
    eng, placed = engine_for_backend(backend_name, cfg, plan, 2)
    _drive_equiv(eng, placed, cfg, n_slots=4)


def _pool_rows_changed(before, after, table, pos, n):
    """Check every layer of every paged leaf: rows (page, offset) that
    differ between two pool snapshots are exactly the written ones —
    positions pos[b]..pos[b]+n-1 of each slot with a live page there —
    or lie in the trash page.  Returns the written (slot, position,
    page, offset) tuples."""
    ps, trash = PS, NPG
    written = [(b, p, int(table[b, p // ps]), p % ps)
               for b in range(table.shape[0])
               for p in range(int(pos[b]), int(pos[b]) + n)
               if p // ps < table.shape[1] and table[b, p // ps] >= 0]
    rows = {(pg, off) for _, _, pg, off in written}
    for seg0, seg1 in zip(before, after):
        for name in ("k", "v"):
            a, c = np.asarray(seg0[name]), np.asarray(seg1[name])
            diff = np.any(a != c, axis=tuple(range(3, a.ndim)))  # (L,P+1,ps)
            for layer in range(a.shape[0]):
                changed = {(int(pg), int(off))
                           for pg, off in zip(*np.nonzero(diff[layer]))
                           if pg != trash}
                assert changed == rows, (name, layer, changed ^ rows)
    return written


@pytest.mark.parametrize("tp", [1, 4], ids=lambda t: f"tp{t}")
def test_paged_step_writes_only_its_rows(tp):
    """The layer scans carry the stacked page pools and update them in
    place: after one decode step and one 3-token verify chunk, every
    layer of every SPD segment differs from its input only at the rows
    written (plus the trash page), each written row holds what the dense
    path holds at the same layer and position, and the logits equal the
    dense path's.  The plan drops blocks 1-2 of 4, so three segments'
    scans each index their pools from 0: a segment-local vs global
    layer index mix-up shows here."""
    cfg = make_cfg("smollm-360m")
    plan = SPDPlanConfig((False, True, True, False))
    assert len(plan.segments()) == 3
    eng, params = engine_for_backend("shard", cfg, plan, tp, dp=1)
    n_slots, prompts = 4, _prompts(cfg)
    nb = len(prompts)
    dense = eng.blank_caches(n_slots, CACHE)
    pool = PagePool(num_pages=NPG, page_size=PS, max_slots=n_slots,
                    pages_per_slot=CACHE // PS)
    pc = eng.blank_paged_caches(n_slots, CACHE, page_size=PS,
                                num_pages=NPG)
    pos = np.zeros(n_slots, np.int32)
    cur = np.zeros((n_slots, 1), np.int32)
    for b, p in enumerate(prompts):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :len(p)] = p
        lg, c1 = eng.prefill(params, jnp.asarray(toks), cache_len=CACHE,
                             lengths=jnp.asarray([len(p)], jnp.int32))
        dense = eng.insert_slot(dense, c1, b)
        assert pool.grow(b, len(p) + 4)
        pc = eng.insert_paged(pc, c1, b, pool.table[b])
        pos[b] = len(p)
        cur[b, 0] = int(np.argmax(np.asarray(lg)[0]))
    table = np.asarray(pool.table)

    def check(before, after, dense_after, n, lg_dense, lg_paged):
        np.testing.assert_allclose(np.asarray(lg_dense)[:nb],
                                   np.asarray(lg_paged)[:nb],
                                   atol=2e-4, rtol=2e-4)
        written = _pool_rows_changed(before, after, table, pos, n)
        assert {b for b, *_ in written} == set(range(nb))
        for seg1, segd in zip(after, dense_after):
            for name in ("k", "v"):
                a, d = np.asarray(seg1[name]), np.asarray(segd[name])
                for b, p, pg, off in written:
                    np.testing.assert_allclose(a[:, pg, off], d[:, b, p],
                                               atol=2e-4, rtol=2e-4)

    snap = jax.tree.map(np.asarray, pc)
    _, l1, dense = eng.decode_with_logits(
        params, jnp.asarray(cur), jnp.asarray(pos), dense)
    _, l2, pc = eng.decode_paged_with_logits(
        params, jnp.asarray(cur), jnp.asarray(pos), jnp.asarray(table), pc)
    check(snap, pc, dense, 1, l1, l2)
    pos[:nb] += 1
    chunk = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (n_slots, 3)).astype(np.int32)
    snap = jax.tree.map(np.asarray, pc)
    l1, dense = eng.verify(params, jnp.asarray(chunk), jnp.asarray(pos),
                           dense)
    l2, pc = eng.verify_paged(params, jnp.asarray(chunk), jnp.asarray(pos),
                              jnp.asarray(table), pc)
    check(snap, pc, dense, 3, l1, l2)


# ---------------------------------------------------------------------------
# Chunked prefill == one-shot prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_full():
    cfg = make_cfg("smollm-360m")
    assert M.supports_chunked_prefill(cfg)
    plan = SPDPlanConfig.first_k(cfg.n_layers, 2)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    tp = 2
    split = simtp.prepare_params(params, cfg, plan, tp)
    eng = SimEngine(cfg, plan, tp, q_chunk=64)
    rng = np.random.default_rng(7)
    for s in (5, 8, 27):              # below/at/above chunk multiples
        p = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
        toks = np.zeros((1, 32), np.int32)
        toks[0, :s] = p
        lg_full, _ = eng.prefill(split, jnp.asarray(toks), cache_len=CACHE,
                                 lengths=jnp.asarray([s], jnp.int32))
        lg_chunk, _ = eng.prefill_chunked(
            split, jnp.asarray(toks[:, :s]), cache_len=CACHE,
            lengths=np.asarray([s]), chunk=8)
        np.testing.assert_allclose(np.asarray(lg_full), np.asarray(lg_chunk),
                                   atol=2e-4, rtol=2e-4)
    # one compilation covers all prompt lengths
    assert sum(1 for k in eng._steps if k[0] == "prefill_chunk") == 1
    # ragged batch: rows finish in different chunks; each row's logits
    # must come from the chunk containing ITS final token
    lens = np.asarray([5, 27])
    toks = np.zeros((2, 32), np.int32)
    for r, s in enumerate(lens):
        toks[r, :s] = rng.integers(0, cfg.vocab_size, s)
    lg_full, _ = eng.prefill(split, jnp.asarray(toks), cache_len=CACHE,
                             lengths=jnp.asarray(lens, jnp.int32))
    lg_chunk, _ = eng.prefill_chunked(split, jnp.asarray(toks),
                                      cache_len=CACHE, lengths=lens, chunk=8)
    np.testing.assert_allclose(np.asarray(lg_full), np.asarray(lg_chunk),
                               atol=2e-4, rtol=2e-4)


def test_chunked_prefill_unsupported_falls_back():
    cfg = make_cfg("mamba2-370m")     # ssm: no chunked path
    assert not M.supports_chunked_prefill(cfg)
    plan = SPDPlanConfig.none(cfg.n_layers)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = SimEngine(cfg, plan, 2, q_chunk=64)
    split = simtp.prepare_params(params, cfg, plan, 2)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 12)).astype(np.int32)
    lg, _ = eng.prefill_chunked(split, jnp.asarray(toks), cache_len=32,
                                lengths=np.asarray([12]), chunk=8)
    lg2, _ = eng.prefill(split, jnp.asarray(toks), cache_len=32,
                         lengths=jnp.asarray([12], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg2))


# ---------------------------------------------------------------------------
# Paged scheduler: soak under pool pressure, preemption, dense equivalence
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    cfg = make_cfg("smollm-360m")
    tp = 2
    plan = SPDPlanConfig.first_k(cfg.n_layers, 2)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    split = simtp.prepare_params(params, cfg, plan, tp)
    eng = SimEngine(cfg, plan, tp, q_chunk=64)
    return cfg, split, eng


def _reqs(cfg, n=6, seed=1, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(uid=uid,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        4 + 5 * uid).astype(np.int32),
                    max_new=max_new) for uid in range(n)]


def test_paged_server_soak_with_preemption(served):
    """Demand (6 requests, up to 35 tokens each) far exceeds the pool
    (6 pages x 8 tokens): every request must still complete, via
    preemption-by-eviction, and match the dense scheduler's outputs."""
    cfg, split, eng = served
    srv = Scheduler(eng, split, CacheConfig(
        cache_len=64, max_batch=4, page_size=8, num_pages=6,
        prefill_chunk=8))
    for r in _reqs(cfg):
        srv.submit(r)
    done = srv.run()
    srv.pool.check()
    assert len(done) == 6
    assert all(len(r.out) == 6 for r in done.values())
    assert srv.n_preemptions > 0          # the pool really was exhausted
    assert srv.pool.num_free == srv.pool.num_pages   # all pages returned

    ref = Scheduler(eng, split, CacheConfig(cache_len=64, max_batch=2))
    for r in _reqs(cfg):
        ref.submit(r)
    ref_done = ref.run()
    for uid in done:
        assert done[uid].out == ref_done[uid].out, uid


def test_spec_paged_truncation_invariants():
    """Draft-token churn against a small pool: after every scheduler
    step the allocator invariants hold and each active slot owns exactly
    the pages its COMMITTED length needs (the speculative suffix the
    verify round rejected has been truncated back to the free list)."""
    from repro.api import LLM, SamplingParams, SpecConfig
    from repro.runtime.paging import pages_for

    llm = LLM.load("smollm-360m-reduced", tp=2, engine="sim",
                   dtype="float32", cache_len=64, max_batch=2,
                   page_size=4, num_pages=12, q_chunk=64,
                   spec=SpecConfig(k=3, draft="all-drop"))
    sched = llm.serve()
    rng = np.random.default_rng(2)
    for uid in range(4):
        sched.submit(Request(
            uid=uid, prompt=rng.integers(0, llm.cfg.vocab_size,
                                         3 + 4 * uid).astype(np.int32),
            max_new=7))
    saw_truncation = False
    steps = 0
    while sched.has_work() and steps < 200:
        sched.step()
        steps += 1
        sched.pool.check()
        for b, r in enumerate(sched.slots):
            if r is None:
                assert int(sched.pool.owned[b]) == 0
                continue
            pos = int(sched.pos[b])
            owned = int(sched.pool.owned[b])
            ps = sched.pool.page_size
            assert pages_for(pos, ps) <= owned <= pages_for(pos + 1, ps), \
                (b, pos, owned)
            if owned == pages_for(pos, ps) < pages_for(pos + 3 + 1, ps):
                saw_truncation = True    # grew for k+1, gave pages back
    assert all(r.done for r in sched.completed.values())
    assert sched.pool.num_free == sched.pool.num_pages
    assert saw_truncation
    assert sched.spec_rounds > 0


def test_paged_server_rejects_oversized(served):
    cfg, split, eng = served
    srv = Scheduler(eng, split, CacheConfig(
        cache_len=64, max_batch=2, page_size=8, num_pages=4))  # 32-token pool
    with pytest.raises(ValueError):
        srv.submit(Request(uid=0,
                           prompt=np.zeros(30, np.int32), max_new=8))


# ---------------------------------------------------------------------------
# Prefix cache through the scheduler: warm admission == cold == dense
# ---------------------------------------------------------------------------


def test_scheduler_prefix_cache_warm_equals_cold(served):
    """A second prompt sharing a page-aligned prefix with an earlier one
    admits through the prefix cache (shared pages + suffix-only prefill)
    and must produce token streams identical to a cold-cache run and to
    the dense scheduler."""
    cfg, split, eng = served
    cc = CacheConfig(cache_len=64, max_batch=2, page_size=8, num_pages=12)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 19).astype(np.int32)
    pa = shared                                        # 2 full pages + 3
    pb = np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size, 4).astype(np.int32)])

    def run_one(srv, uid, p):
        srv.submit(Request(uid=uid, prompt=p, max_new=5))
        return srv.run()[uid].out

    # cold reference: a fresh pool per request, nothing resident
    cold = [run_one(Scheduler(eng, split, cc), 0, p) for p in (pa, pb)]
    # dense reference
    dsrv = Scheduler(eng, split, CacheConfig(cache_len=64, max_batch=2))
    dense = [run_one(dsrv, i, p) for i, p in enumerate((pa, pb))]
    # warm: one scheduler, sequential — pb's admission must share pa's
    # two full prompt pages (cached after pa's slot released) and
    # prefill only the suffix
    srv = Scheduler(eng, split, cc)
    assert srv.kv.prefix_cache
    o1 = run_one(srv, 0, pa)
    assert srv.kv.prefix_hits == 0
    o2 = run_one(srv, 1, pb)
    assert srv.kv.prefix_hits == 1
    assert srv.kv.prefix_tokens_reused == 16           # 2 pages x 8 tokens
    assert [o1, o2] == cold == dense
    srv.pool.check()
    # and an identical-prompt resubmission hits the same pages again
    o3 = run_one(srv, 2, pb)
    assert o3 == o2 and srv.kv.prefix_hits == 2
    srv.pool.check()


def test_prefix_cache_off_by_config(served):
    """prefix_cache=False forces cold admission for every request."""
    cfg, split, eng = served
    srv = Scheduler(eng, split, CacheConfig(
        cache_len=64, max_batch=2, page_size=8, num_pages=12,
        prefix_cache=False))
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    for uid in range(2):
        srv.submit(Request(uid=uid, prompt=p, max_new=3))
    done = srv.run()
    assert done[0].out == done[1].out
    assert srv.kv.prefix_queries == 0 and srv.kv.prefix_hits == 0
