"""Hypothesis property soak for the unified api.scheduler.Scheduler.

Random admission/completion/cancellation sequences against a paged
scheduler with a deliberately tiny page pool (so preemption-by-eviction
fires constantly) must preserve the allocator/scheduler invariants — no
page leaks, no page double-ownership, no slot aliasing, queue/slots
disjoint — and every request's greedy token stream must equal running it
alone.

The model execution is a deterministic FakeEngine implementing the
engine contract with the token recurrence

    next(seq) = (seq[-1] * 31 + len(seq)) % V

so the per-request reference stream is computable in closed form AND
depends on the full (prompt + generated) sequence — a scheduler that
mixes up slots, feeds a stale `cur`/`pos`, or resumes a preempted
request with the wrong tokens produces a detectably different stream.
A smaller real-engine cross-check (batch vs unbatched LLM.generate under
pool pressure) closes the loop on the actual decode path.

`make test-soak` raises the example budget via SOAK_EXAMPLES.
"""
import os
from unittest import mock

import numpy as np
import pytest

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:                       # property tests skip, the
    hypothesis = None                     # real-engine cross-check runs

    def _skip_deco(*a, **k):
        def deco(f):
            return pytest.mark.skip(reason="hypothesis not installed")(f)
        return deco

    given = settings = _skip_deco

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

import jax.numpy as jnp

from repro.api.scheduler import (CacheConfig, InvalidRequestError, Request,
                                 Scheduler)

V = 97
EXAMPLES = int(os.environ.get("SOAK_EXAMPLES", "25"))


def _next_tok(last: int, seqlen_after: int) -> int:
    return (last * 31 + seqlen_after) % V


def reference_stream(prompt, max_new: int):
    """Closed-form greedy stream of the FakeEngine recurrence."""
    seq = list(prompt)
    out = []
    for _ in range(max_new):
        out.append(_next_tok(seq[-1], len(seq) + 1))
        seq.append(out[-1])
    return out


class FakeEngine:
    """Deterministic engine-contract stub (see module docstring)."""

    def blank_caches(self, batch, cache_len):
        return jnp.zeros((1,))

    def blank_paged_caches(self, max_slots, cache_len, *, page_size,
                           num_pages):
        return jnp.zeros((1,))

    def insert_slot(self, caches, caches1, b):
        return caches

    def insert_paged(self, pcaches, caches1, b, page_row):
        return pcaches

    def prefill(self, params, toks, *, cache_len, lengths, embeds=None):
        s = int(np.asarray(lengths)[0])
        last = int(np.asarray(toks)[0, s - 1])
        logits = np.full((1, V), -1.0, np.float32)
        logits[0, _next_tok(last, s + 1)] = 1.0
        return jnp.asarray(logits), jnp.zeros((1,))

    def _dec(self, cur, pos):
        cur = np.asarray(cur)[:, 0]
        pos = np.asarray(pos)
        nxt = (cur * 31 + pos + 2) % V
        return jnp.asarray(nxt[:, None].astype(np.int32))

    # decode writes position pos (the cur token's slot); the produced
    # token extends the sequence to length pos+2 counting from 0
    def decode(self, params, cur, pos, caches):
        return self._dec(cur, pos), caches

    def decode_paged(self, params, cur, pos, page_table, pcaches):
        return self._dec(cur, pos), pcaches

    # speculative verify: one-hot next-token logits for every chunk
    # position (chain token toks[:, j] sits at absolute position pos + j;
    # a tree chunk's column j sits at pos + depths[j] instead, which is
    # what makes a depth-1 alternative score like a second position-1)
    def verify(self, params, toks, pos, caches, tree=None):
        toks = np.asarray(toks)
        pos = np.asarray(pos)
        b, c = toks.shape
        depths = tree[0] if tree is not None else tuple(range(c))
        logits = np.full((b, c, V), -1.0, np.float32)
        for j in range(c):
            nxt = (toks[:, j] * 31 + pos + depths[j] + 2) % V
            logits[np.arange(b), j, nxt] = 1.0
        return jnp.asarray(logits), caches

    def verify_paged(self, params, toks, pos, page_table, pcaches,
                     tree=None):
        lg, _ = self.verify(params, toks, pos, None, tree=tree)
        return lg, pcaches


class FakeDrafter:
    """Drafter-contract stub over the same closed-form recurrence, with
    a deterministic corruption: every position divisible by 3 proposes a
    WRONG token.  The verify round must reject exactly there, so spec
    scheduling exercises partial acceptance, rollback/truncation, and
    preemption/cancel of requests carrying unverified draft tokens —
    while the committed greedy streams stay equal to the reference.

    With `tree_width` > 1 the first-position ALTERNATIVE is the correct
    token exactly when the chain draft is corrupted (and a wrong token
    otherwise), so tree rounds deterministically exercise BOTH the
    alt-commit recovery path (rejected chain -> alt + bonus) and plain
    alt-miss rejections."""

    def __init__(self, max_batch):
        self.pos = np.zeros(max_batch, np.int32)

    def insert(self, b, toks, caches1=None):
        self.pos[b] = len(toks)

    def draft(self, ctx, start, k, *, greedy=False, tree_width=1,
              sampling=None):
        ctx = np.asarray(ctx)
        start = np.asarray(start)
        base = start + ctx.shape[1] - 1
        cur = ctx[:, -1].copy()
        toks = []
        alts = None
        for i in range(k):
            p = base + i
            nxt = (cur * 31 + p + 2) % V
            prop = np.where(p % 3 == 0, (nxt + 1) % V, nxt)
            if i == 0 and tree_width > 1:
                alt = np.where(p % 3 == 0, nxt, (nxt + 1) % V)
                alts = np.stack([alt] * (tree_width - 1),
                                1).astype(np.int32)
            toks.append(prop.astype(np.int32))
            cur = prop
        return np.stack(toks, 1), None, alts


def _check_invariants(sched: Scheduler):
    sched.kv.pool.check()      # free-list/page-table invariants
    active = [r for r in sched.slots if r is not None]
    # no slot aliasing: a request object occupies at most one slot
    assert len({id(r) for r in active}) == len(active)
    # queue and slots are disjoint
    qids = {id(r) for r in sched.queue}
    assert not qids & {id(r) for r in active}
    # inactive slots own no pages
    for b, r in enumerate(sched.slots):
        if r is None:
            assert int(sched.kv.pool.owned[b]) == 0, b
    # completed requests are flagged done and hold no slot
    for r in sched.completed.values():
        assert r.done and id(r) not in {id(a) for a in active}


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.data())
def test_scheduler_random_ops_soak(data):
    cc = CacheConfig(cache_len=32, max_batch=3, page_size=4, num_pages=9)
    sched = Scheduler(FakeEngine(), None, cc)
    submitted, cancelled = [], []
    uid = 0
    n_ops = data.draw(st.integers(4, 18), label="n_ops")
    for _ in range(n_ops):
        op = data.draw(st.sampled_from(["submit", "step", "steps",
                                        "cancel"]), label="op")
        if op == "submit":
            plen = data.draw(st.integers(1, 12), label="plen")
            max_new = data.draw(st.integers(1, 8), label="max_new")
            prompt = np.asarray(
                data.draw(st.lists(st.integers(0, V - 1), min_size=plen,
                                   max_size=plen), label="prompt"),
                np.int32)
            req = Request(uid=uid, prompt=prompt, max_new=max_new)
            uid += 1
            try:
                sched.submit(req)
                submitted.append(req)
            except InvalidRequestError:
                # only over-capacity requests may be rejected
                assert plen + max_new > cc.cache_len \
                    or not sched.kv.pool.fits_alone(plen + max_new)
        elif op == "cancel" and submitted:
            idx = data.draw(st.integers(0, len(submitted) - 1), label="ci")
            req = submitted.pop(idx)
            sched.cancel([req])
            cancelled.append(req)
        else:
            k = 1 if op == "step" else data.draw(st.integers(2, 5),
                                                 label="k")
            for _ in range(k):
                sched.step()
        _check_invariants(sched)

    # drain to completion; every surviving request finishes
    sched.run(max_steps=500)
    _check_invariants(sched)
    for req in submitted:
        assert req.done, req.uid
        # greedy stream identical to running the request unbatched —
        # through any number of preemptions/resumes
        assert req.out == reference_stream(req.prompt, req.max_new), \
            (req.uid, req.n_preempted)
    for req in cancelled:
        assert req.uid not in sched.completed
    # no page leaks once everything drained
    assert sched.kv.pool.num_free == cc.num_pages


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.data())
def test_scheduler_spec_soak(data):
    """The random-ops soak with speculative decoding on: draft-token
    churn (partial acceptance every round), cancel of requests holding
    unverified drafts, preemption under pool pressure mid-speculation —
    invariants must hold after every op and the committed greedy streams
    must still equal the closed-form reference."""
    from repro.spec import SpecState

    cc = CacheConfig(cache_len=32, max_batch=3, page_size=4, num_pages=9)
    k = data.draw(st.integers(1, 3), label="k")
    sched = Scheduler(FakeEngine(), None, cc,
                      spec=SpecState(k=k, drafter=FakeDrafter(cc.max_batch)))
    submitted, cancelled = [], []
    uid = 0
    for _ in range(data.draw(st.integers(4, 14), label="n_ops")):
        op = data.draw(st.sampled_from(["submit", "step", "steps",
                                        "cancel"]), label="op")
        if op == "submit":
            plen = data.draw(st.integers(1, 12), label="plen")
            max_new = data.draw(st.integers(1, 8), label="max_new")
            prompt = np.asarray(
                data.draw(st.lists(st.integers(0, V - 1), min_size=plen,
                                   max_size=plen), label="prompt"),
                np.int32)
            req = Request(uid=uid, prompt=prompt, max_new=max_new)
            uid += 1
            try:
                sched.submit(req)
                submitted.append(req)
            except InvalidRequestError:
                assert plen + max_new > cc.cache_len \
                    or not sched.kv.pool.fits_alone(plen + max_new)
        elif op == "cancel" and submitted:
            req = submitted.pop(
                data.draw(st.integers(0, len(submitted) - 1), label="ci"))
            sched.cancel([req])
            cancelled.append(req)
        else:
            for _ in range(1 if op == "step"
                           else data.draw(st.integers(2, 4), label="k2")):
                sched.step()
        _check_invariants(sched)

    sched.run(max_steps=500)
    _check_invariants(sched)
    for req in submitted:
        assert req.done, req.uid
        assert req.out == reference_stream(req.prompt, req.max_new), \
            (req.uid, req.n_preempted, req.n_drafted, req.n_draft_accepted)
        assert req.n_draft_accepted <= req.n_drafted
    for req in cancelled:
        assert req.uid not in sched.completed
    assert sched.kv.pool.num_free == cc.num_pages
    assert sched.spec_accepted <= sched.spec_drafted
    if sched.spec_row_rounds:
        # every verify round commits at least one target-approved token
        assert sched.spec_tokens_per_step >= 1.0


@settings(max_examples=EXAMPLES, deadline=None)
@given(st.data())
def test_scheduler_adaptive_tree_soak(data):
    """The spec soak with ADAPTIVE per-request budgets and (when the
    window allows it) depth-1 TREE rounds: per-slot k oscillates as
    FakeDrafter's corruption pattern alternates full-accept and
    zero-accept rounds, tree alt-commits trigger the paged alt-KV
    relocation + `PagePool.shrink` rollback, and requests are cancelled
    or preempted mid-round — all while every committed greedy stream
    still equals the closed-form reference and the free-list invariants
    hold after every op."""
    from repro.api import scheduler as SCH
    from repro.spec import SpecState

    # FakeDrafter's alt is the correct token exactly when the chain's
    # first draft is wrong, so every row round that is offered an alt
    # and rejects its first draft must commit through the alt.  Rounds
    # with under two tokens of budget left or no room for the alt's KV
    # are offered none (chain acceptance), so a short stream may never
    # reach a recoverable rejection.
    recoverable = []

    def spy(draft, alts, argmax, alt_argmax):
        if alts is not None and len(draft) and draft[0] != argmax[0]:
            recoverable.append(1)
        return accept_tree(draft, alts, argmax, alt_argmax)

    accept_tree = SCH.accept_greedy_tree
    with mock.patch.object(SCH, "accept_greedy_tree", spy):
        cc = CacheConfig(cache_len=32, max_batch=3, page_size=4, num_pages=9)
        k_min = data.draw(st.integers(1, 2), label="k_min")
        k_max = data.draw(st.integers(k_min, 4), label="k_max")
        k0 = data.draw(st.integers(k_min, k_max), label="k0")
        width = data.draw(st.integers(1, min(2, k_min + 1)), label="width")
        sched = Scheduler(FakeEngine(), None, cc,
                          spec=SpecState(k=k0,
                                         drafter=FakeDrafter(cc.max_batch),
                                         adaptive=True, k_min=k_min,
                                         k_max=k_max, tree_width=width))
        submitted, cancelled = [], []
        uid = 0
        kb_seen = set()
        for _ in range(data.draw(st.integers(4, 14), label="n_ops")):
            op = data.draw(st.sampled_from(["submit", "step", "steps",
                                            "cancel"]), label="op")
            if op == "submit":
                plen = data.draw(st.integers(1, 12), label="plen")
                max_new = data.draw(st.integers(1, 8), label="max_new")
                prompt = np.asarray(
                    data.draw(st.lists(st.integers(0, V - 1), min_size=plen,
                                       max_size=plen), label="prompt"),
                    np.int32)
                req = Request(uid=uid, prompt=prompt, max_new=max_new)
                uid += 1
                try:
                    sched.submit(req)
                    submitted.append(req)
                except InvalidRequestError:
                    assert plen + max_new > cc.cache_len \
                        or not sched.kv.pool.fits_alone(plen + max_new)
            elif op == "cancel" and submitted:
                req = submitted.pop(
                    data.draw(st.integers(0, len(submitted) - 1), label="ci"))
                sched.cancel([req])
                cancelled.append(req)
            else:
                for _ in range(1 if op == "step"
                               else data.draw(st.integers(2, 4), label="k2")):
                    sched.step()
            # adaptive budgets never escape [k_min, k_max]
            for b, r in enumerate(sched.slots):
                if r is not None:
                    kb = int(sched._spec_kb[b])
                    assert k_min <= kb <= k_max, (kb, k_min, k_max)
                    kb_seen.add(kb)
            _check_invariants(sched)

        sched.run(max_steps=500)
        _check_invariants(sched)
        for req in submitted:
            assert req.done, req.uid
            assert req.out == reference_stream(req.prompt, req.max_new), \
                (req.uid, req.n_preempted, req.n_drafted, req.n_draft_accepted)
        for req in cancelled:
            assert req.uid not in sched.completed
        assert sched.kv.pool.num_free == cc.num_pages
    assert sched.spec_alt_commits == len(recoverable)


@settings(max_examples=max(5, EXAMPLES // 5), deadline=None)
@given(st.data())
def test_scheduler_dense_soak(data):
    """Same soak on the dense (per-slot cache) degenerate case."""
    cc = CacheConfig(cache_len=16, max_batch=2)
    sched = Scheduler(FakeEngine(), None, cc)
    reqs = []
    for i in range(data.draw(st.integers(1, 6), label="n")):
        plen = data.draw(st.integers(1, 8), label="plen")
        prompt = np.asarray([data.draw(st.integers(0, V - 1))] * plen,
                            np.int32)
        req = Request(uid=i, prompt=prompt,
                      max_new=data.draw(st.integers(1, 6), label="mn"))
        sched.submit(req)
        reqs.append(req)
        if data.draw(st.booleans(), label="interleave"):
            sched.step()
    sched.run(max_steps=200)
    for req in reqs:
        assert req.done
        assert req.out == reference_stream(req.prompt, req.max_new)


def test_real_engine_batch_matches_unbatched():
    """Real decode path: batched paged serving under pool pressure (with
    preemptions) produces the same greedy streams as one-at-a-time."""
    from repro.api import LLM, SamplingParams

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, int(n)).astype(np.int32)
               for n in rng.integers(3, 10, 5)]
    sp = SamplingParams(max_new=5)

    def outs(llm):
        return [o.token_ids for o in llm.generate(prompts, sp)]

    llm = LLM.load("smollm-360m-reduced", tp=2, engine="sim",
                   dtype="float32", cache_len=32, max_batch=3,
                   page_size=4, num_pages=10)
    batched = outs(llm)
    assert llm.serve().n_preemptions >= 0
    single = []
    for p in prompts:
        o = llm.generate([p], sp)[0]
        single.append(o.token_ids)
    assert batched == single
