"""`repro.api` facade: LLM/SamplingParams/Scheduler.

Covers the acceptance criteria of the facade PR: greedy parity with a
directly driven Scheduler (the pre-facade Server protocol; the legacy
`runtime.server` shims themselves are deleted — import-error-locked
below), sim-vs-shard engine parity through `LLM.generate`, top-k/top-p
sampling determinism under fixed per-request seeds, admission
validation with typed errors, chunked prefill on the DENSE path,
streaming, the jitted sampling kernel itself, and backend-registry
resolution of `LLM.load(engine=...)`."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cfg
from repro.api import (CacheConfig, InvalidRequestError, LLM, Request,
                       SamplingParams, Scheduler)
from repro.config.base import SPDPlanConfig
from repro.core import model as M
from repro.runtime import sampling as RS

MAXNEW = 5


# ---------------------------------------------------------------------------
# The jitted sampling kernel
# ---------------------------------------------------------------------------


def _keys(n, seed=0):
    return RS.make_keys(np.full(n, seed, np.int32),
                        np.arange(n, dtype=np.int32))


def test_sample_core_greedy_and_filters():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    ref = np.asarray(jnp.argmax(logits, -1))
    zeros = np.zeros(4, np.float32)
    ones_p = np.ones(4, np.float32)
    k0 = np.zeros(4, np.int32)
    # temperature 0 == greedy regardless of key
    out = RS.sample_tokens(logits, zeros, k0, ones_p, _keys(4))
    np.testing.assert_array_equal(np.asarray(out), ref)
    # top_k=1 and tiny top_p each collapse sampling to argmax
    hot = np.full(4, 2.0, np.float32)
    out = RS.sample_tokens(logits, hot, np.ones(4, np.int32), ones_p,
                           _keys(4, seed=3))
    np.testing.assert_array_equal(np.asarray(out), ref)
    out = RS.sample_tokens(logits, hot, k0, np.full(4, 1e-4, np.float32),
                           _keys(4, seed=5))
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_sample_core_topk_restricts_support():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
    top8 = set(np.asarray(jnp.argsort(logits[0])[::-1][:8]).tolist())
    t = np.asarray([1.5], np.float32)
    k = np.asarray([8], np.int32)
    p = np.asarray([1.0], np.float32)
    seen = set()
    for s in range(50):
        key = RS.make_keys(np.asarray([s], np.int32),
                           np.asarray([0], np.int32))
        tok = int(np.asarray(RS.sample_tokens(logits, t, k, p, key))[0])
        assert tok in top8, (tok, top8)
        seen.add(tok)
    assert len(seen) > 1          # it actually samples, not argmaxes


def test_sample_core_deterministic_in_key():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((3, 32)), jnp.float32)
    t = np.full(3, 0.9, np.float32)
    k = np.full(3, 10, np.int32)
    p = np.full(3, 0.9, np.float32)
    a = np.asarray(RS.sample_tokens(logits, t, k, p, _keys(3, seed=7)))
    b = np.asarray(RS.sample_tokens(logits, t, k, p, _keys(3, seed=7)))
    np.testing.assert_array_equal(a, b)


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(max_new=0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_cache_config_validation():
    with pytest.raises(ValueError):
        CacheConfig(cache_len=64, page_size=8)        # num_pages missing
    with pytest.raises(ValueError):
        CacheConfig(cache_len=60, page_size=8, num_pages=4)  # not multiple
    assert not CacheConfig(cache_len=64).paged
    assert CacheConfig(cache_len=64, page_size=8, num_pages=4).paged


# ---------------------------------------------------------------------------
# The LLM facade on the sim engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm_sim():
    cfg = make_cfg("smollm-360m")
    return LLM.load(cfg, tp=2, engine="sim",
                    plan=SPDPlanConfig.first_k(cfg.n_layers, 2),
                    cache_len=64, max_batch=2, q_chunk=64, seed=0)


def _prompts(cfg, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, 4 + 3 * i).astype(np.int32)
            for i in range(n)]


def test_generate_greedy_matches_direct_scheduler(llm_sim):
    """Regression lock: LLM.generate == driving a fresh Scheduler over
    the same engine by hand (the pre-facade dense Server protocol)."""
    prompts = _prompts(llm_sim.cfg)
    outs = llm_sim.generate(prompts, SamplingParams(max_new=MAXNEW))
    srv = Scheduler(llm_sim.engine, llm_sim.params,
                    CacheConfig(cache_len=64, max_batch=2))
    for i, p in enumerate(prompts):
        srv.submit(Request(uid=i, prompt=p, max_new=MAXNEW))
    done = srv.run()
    for i, o in enumerate(outs):
        assert o.token_ids == done[i].out, i
        assert o.finish_reason == "length"
        assert o.prompt_token_ids == [int(t) for t in prompts[i]]


def test_generate_drains_queue_when_admissions_finish_at_once():
    """Requests that finish on their admission token free every slot in
    the same step; the queued rest must still run (a step that admits
    and finishes everything used to report no work left)."""
    cfg = make_cfg("smollm-360m")
    llm = LLM.load(cfg, tp=1, engine="sim", cache_len=16, max_batch=3)
    outs = llm.generate([[0]] * 4, SamplingParams(max_new=1))
    assert [len(o.token_ids) for o in outs] == [1] * 4
    assert all(o.finish_reason == "length" for o in outs)


def test_legacy_server_module_removed():
    """The deprecated `runtime/server.py` Server/PagedServer shims
    (deprecated PR 2, warning since PR 4) are GONE: importing the module
    must fail, so nothing can silently depend on it again."""
    with pytest.raises(ImportError):
        importlib.import_module("repro.runtime.server")


def test_llm_load_resolves_backend_registry():
    """LLM.load(engine=) goes through the parallel-backend registry:
    both built-ins resolve, unknown names fail fast and name the
    registered backends."""
    from repro.parallel.backend import (ParallelBackend, backend_names,
                                        resolve_backend,
                                        resolved_backend_name)
    assert {"sim", "shard"} <= set(backend_names())
    for name in backend_names():
        assert issubclass(resolve_backend(name), ParallelBackend)
        assert resolved_backend_name(name).startswith(f"{name}/")
    cfg = make_cfg("smollm-360m")
    with pytest.raises(ValueError, match="unknown engine"):
        LLM.load(cfg, tp=2, engine="nope", cache_len=16)
    with pytest.raises(ValueError, match="dp must be 1"):
        LLM.load(cfg, tp=2, dp=2, engine="sim", cache_len=16)


def test_paged_scheduler_matches_dense(llm_sim):
    prompts = _prompts(llm_sim.cfg)
    ref = [o.token_ids
           for o in llm_sim.generate(prompts, SamplingParams(max_new=MAXNEW))]
    sched = llm_sim.serve(max_batch=3, page_size=8, num_pages=12,
                          prefill_chunk=8)
    assert isinstance(sched, Scheduler) and sched.kv.paged
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=p, max_new=MAXNEW))
    done = sched.run()
    assert [done[i].out for i in range(len(prompts))] == ref


def test_prefill_chunk_routed_on_dense_path(llm_sim):
    """--prefill-chunk used to be silently ignored on the dense path;
    the unified scheduler must honor it and produce identical tokens."""
    prompts = _prompts(llm_sim.cfg)
    ref = [o.token_ids
           for o in llm_sim.generate(prompts, SamplingParams(max_new=MAXNEW))]
    sched = llm_sim.serve(prefill_chunk=8)     # dense + chunked prefill
    assert not sched.kv.paged and sched.prefill_chunk == 8
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=p, max_new=MAXNEW))
    done = sched.run()
    assert [done[i].out for i in range(len(prompts))] == ref


def test_sampling_deterministic_per_seed(llm_sim):
    prompts = _prompts(llm_sim.cfg)
    sp = SamplingParams(temperature=0.8, top_k=12, top_p=0.9, seed=123,
                        max_new=MAXNEW)
    a = [o.token_ids for o in llm_sim.generate(prompts, sp)]
    b = [o.token_ids for o in llm_sim.generate(prompts, sp)]
    assert a == b
    for toks in a:
        assert len(toks) == MAXNEW
        assert all(0 <= t < llm_sim.cfg.vocab_size for t in toks)
    # mixed batch: greedy rows stay greedy alongside sampled rows
    greedy_ref = [o.token_ids
                  for o in llm_sim.generate(prompts,
                                            SamplingParams(max_new=MAXNEW))]
    mixed = llm_sim.generate(prompts[:2], [SamplingParams(max_new=MAXNEW),
                                           sp])
    assert mixed[0].token_ids == greedy_ref[0]
    assert mixed[1].token_ids == b[1]


def test_stop_tokens(llm_sim):
    prompts = _prompts(llm_sim.cfg, n=1)
    ref = llm_sim.generate(prompts, SamplingParams(max_new=MAXNEW))[0]
    stop = ref.token_ids[2]
    out = llm_sim.generate(
        prompts, SamplingParams(max_new=MAXNEW,
                                stop_token_ids=(stop,)))[0]
    idx = ref.token_ids.index(stop)
    assert out.token_ids == ref.token_ids[: idx + 1]
    assert out.finish_reason == "stop"


def test_streaming_matches_generate(llm_sim):
    prompts = _prompts(llm_sim.cfg)
    ref = llm_sim.generate(prompts, SamplingParams(max_new=MAXNEW))
    events = list(llm_sim.generate_stream(prompts,
                                          SamplingParams(max_new=MAXNEW)))
    per = {i: [] for i in range(len(prompts))}
    for e in events:
        per[e.index].append(e.token_id)
        if e.done:
            assert e.finish_reason == "length"
    assert [per[i] for i in range(len(prompts))] \
        == [r.token_ids for r in ref]


def test_admission_validation_typed_errors(llm_sim):
    sched = llm_sim.serve(page_size=8, num_pages=4)    # 32-token pool
    with pytest.raises(InvalidRequestError):
        sched.submit(Request(uid=0, prompt=np.zeros(0, np.int32)))
    with pytest.raises(InvalidRequestError):
        sched.submit(Request(uid=1, prompt=np.zeros(4, np.int32),
                             max_new=0))
    with pytest.raises(InvalidRequestError):           # prompt > cache_len
        sched.submit(Request(uid=2, prompt=np.zeros(65, np.int32)))
    with pytest.raises(InvalidRequestError):           # beyond pool
        sched.submit(Request(uid=3, prompt=np.zeros(30, np.int32),
                             max_new=8))
    assert not sched.queue                             # nothing enqueued
    # facade batches are all-or-nothing: a bad prompt rejects the batch
    good = np.zeros(4, np.int32)
    with pytest.raises(InvalidRequestError):
        llm_sim.generate([good, np.zeros(0, np.int32)])
    assert not llm_sim.serve().queue


def test_bucket_capped_and_boundary_capacity():
    """Two admission edge cases: a prompt whose power-of-two bucket
    exceeds cache_len must not build oversized caches, and a request
    writing exactly up to the last cache position (prompt + max_new - 1
    == cache_len) must be admitted, as the legacy dense Server did."""
    cfg = make_cfg("smollm-360m")
    llm = LLM.load(cfg, tp=2, engine="sim", cache_len=96, max_batch=2,
                   q_chunk=64)
    sched = llm.serve(page_size=8, num_pages=24)
    sched.submit(Request(uid=0, prompt=np.ones(70, np.int32),
                         max_new=8))                 # _bucket(70) = 128
    assert len(sched.run()[0].out) == 8
    sched.pool.check()
    out = llm.generate([np.ones(92, np.int32)],      # 92 + 5 - 1 == 96
                       SamplingParams(max_new=5))[0]
    assert len(out.token_ids) == 5
    with pytest.raises(InvalidRequestError):         # one past the edge
        llm.serve().submit(Request(uid=1, prompt=np.ones(92, np.int32),
                                   max_new=6))


def test_apply_spd_facade_rewires_plan():
    cfg = make_cfg("smollm-360m")
    llm = LLM.load(cfg, tp=2, engine="sim", cache_len=64, max_batch=2,
                   q_chunk=64, seed=0)
    assert llm.plan.n_dropped == 0
    from repro.data.synthetic import calibration_batches
    calib = calibration_batches(cfg.vocab_size, 8, 32, batch=4)[:1]
    report = llm.apply_spd(calib, n_spd=1, tau1=1e9, tau2=2e9,
                           strategies=("ZS",))        # ISB-only: no distill
    assert llm.plan.n_dropped == 1
    assert list(report.chosen) == [int(report.ranking[0])]
    out = llm.generate(_prompts(cfg, n=1),
                       SamplingParams(max_new=3))[0]
    assert len(out.token_ids) == 3


# ---------------------------------------------------------------------------
# Sim vs shard engine parity through the facade
# ---------------------------------------------------------------------------


def test_generate_parity_sim_vs_shard():
    cfg = make_cfg("smollm-360m")
    plan = SPDPlanConfig.first_k(cfg.n_layers, 2)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, n=2)
    kw = dict(plan=plan, params=params, cache_len=64, max_batch=2,
              q_chunk=64)
    llm_sim = LLM.load(cfg, tp=2, engine="sim", **kw)
    llm_shard = LLM.load(cfg, tp=2, dp=2, engine="shard", **kw)
    greedy = SamplingParams(max_new=4)
    a = [o.token_ids for o in llm_sim.generate(prompts, greedy)]
    b = [o.token_ids for o in llm_shard.generate(prompts, greedy)]
    assert a == b
    sp = SamplingParams(temperature=0.7, top_k=10, seed=7, max_new=4)
    c = [o.token_ids for o in llm_sim.generate(prompts, sp)]
    d = [o.token_ids for o in llm_shard.generate(prompts, sp)]
    assert c == d
