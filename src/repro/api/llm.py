"""`LLM` — the one public way to load and run a model.

Every consumer used to hand-roll the engine-specific parameter dance
(`simtp.prepare_params` for SimEngine vs `pad_model` → `stack_segments`
→ `device_put` with `TP.param_pspecs` for ShardEngine) and pick between
two schedulers.  `LLM.load` resolves the config, initializes (or
accepts) canonical params, performs the correct placement, and exposes:

    generate(prompts, sampling)  -> list[RequestOutput]
    generate_stream(...)         -> iterator of StreamEvent
    serve(...)                   -> a ready `Scheduler` (dense or paged)
    apply_spd(calib, ...)        -> paper pipeline (sensitivity ->
                                    ZS/B2B/HG) + redeployment, in place

Example:

    from repro.api import LLM, SamplingParams
    llm = LLM.load("smollm-360m-reduced", tp=2, engine="sim",
                   dtype="float32", cache_len=64)
    outs = llm.generate(prompts, SamplingParams(max_new=8))

Note on devices: engine="shard" builds a (dp, tp) mesh over the first
dp*tp devices JAX exposes.  On a TPU host those are the chips, ordered
along the interconnect (launch/mesh.py); the host-device-count flag
(`XLA_FLAGS=--xla_force_host_platform_device_count=N`, set before jax
initializes) only shapes the CPU backend, where it provides N virtual
devices.  engine="sim" simulates TP with vmap on a single device and
requires dp == 1.

Canonical weights live on the host (the CPU device) and each placement
is built there, so an accelerator only ever holds placed shards.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np

from repro.api.outputs import RequestOutput, StreamEvent
from repro.api.sampling import SamplingParams
from repro.api.scheduler import CacheConfig, Request, Scheduler
from repro.config.base import (CommPolicy, ModelConfig, SPDPlanConfig,
                               SYNC_LEVELS, replace)


def _host():
    """The CPU device that holds canonical weights and builds placements."""
    import jax
    return jax.local_devices(backend="cpu")[0]


def _resolve_comm(comm, n_layers: int,
                  logits: str = "exact") -> Optional[CommPolicy]:
    """None | CommPolicy | level string -> CommPolicy (None = all exact;
    a None/"exact" comm still honors a non-exact `logits` level)."""
    if isinstance(comm, CommPolicy):
        return comm
    if comm is None:
        comm = "exact"
    if isinstance(comm, str):
        if comm not in SYNC_LEVELS:
            raise ValueError(f"comm={comm!r}: expected a CommPolicy or one "
                             f"of {SYNC_LEVELS}")
        if comm == "exact" and logits == "exact":
            return None
        return CommPolicy.uniform(n_layers, comm, logits=logits)
    raise TypeError(f"comm must be None, a str, or CommPolicy: {comm!r}")


def _as_prompts(prompts) -> List[np.ndarray]:
    """Normalize one prompt or a batch of prompts to a list of (S,) i32.
    Accepts a single token sequence, a list of sequences, or a 1-D/2-D
    ndarray (rows = prompts)."""
    if isinstance(prompts, np.ndarray):
        prompts = [prompts] if prompts.ndim == 1 else list(prompts)
    elif len(prompts) and isinstance(prompts[0], (int, np.integer)):
        prompts = [prompts]
    return [np.asarray(p, np.int32) for p in prompts]


def _per_request(sampling, n: int) -> List[SamplingParams]:
    if sampling is None:
        sampling = SamplingParams()
    if isinstance(sampling, SamplingParams):
        return [sampling] * n
    if len(sampling) != n:
        raise ValueError(f"got {len(sampling)} SamplingParams for "
                         f"{n} prompts")
    return list(sampling)


class LLM:
    """A loaded model + engine + placed params behind one object.

    Construct with `LLM.load(...)`; the constructor itself is an
    implementation detail.
    """

    def __init__(self, cfg, plan, engine_kind, engine, params, canonical,
                 cache: CacheConfig, *, mesh=None, tp: int, dp: int,
                 q_chunk: int, dp_replicas: int = 1,
                 router: str = "least-outstanding", obs=None):
        from repro.obs.recorder import NULL_RECORDER
        self.obs = obs if obs is not None else NULL_RECORDER
        self.cfg = cfg
        self.plan = plan
        self.engine_kind = engine_kind
        self.engine = engine
        self.params = params          # engine-placed (split or sharded)
        self.canonical = canonical    # canonical tree on the host CPU device
        self.cache = cache
        self.mesh = mesh
        self.tp, self.dp, self.q_chunk = tp, dp, q_chunk
        # DP-over-TP cluster serving (docs/cluster.md): >1 makes serve()
        # return a ClusterRouter over dp_replicas weight-shared replicas
        self.dp_replicas = dp_replicas
        self.router_policy = router
        # self-speculative decoding (docs/speculative.md): the draft is
        # these same canonical weights placed under a cheaper comm plan
        self.spec = None              # SpecConfig or None
        self.draft_plan = None
        self.draft_engine = None
        self.draft_params = None
        self.spec_calibration = None  # CalibrationResult ("calibrated")
        self._sched: Optional[Scheduler] = None
        # facade-internal uids are negative so they never collide with
        # user-chosen uids of Requests submitted directly to serve()
        self._next_uid = -1

    # ---------------- construction ----------------

    @classmethod
    def load(cls, arch, *, tp: int = 1, dp: int = 1, engine: str = "sim",
             spd: float = 0.0, plan: Optional[SPDPlanConfig] = None,
             comm=None, comm_logits: str = "exact",
             page_size: Optional[int] = None,
             num_pages: Optional[int] = None,
             prefill_chunk: Optional[int] = None,
             cache_len: int = 128, max_batch: int = 4,
             dtype: Optional[str] = None, seed: int = 0, params=None,
             q_chunk: int = 64, mesh=None, spec=None,
             dp_replicas: int = 1,
             router: str = "least-outstanding", obs=None) -> "LLM":
        """Load `arch` (config name or ModelConfig) onto an engine.

        engine     a parallel-backend registry name
                   (`repro.parallel.backend.backend_names()`): "sim"
                   (vmap simulated TP, one device) or "shard"
                   (shard_map over a dp x tp mesh); a newly registered
                   backend is loadable here by its name.
        spd        fraction of blocks to SPD-drop (first-k plan) —
                   ignored when an explicit `plan` is given; use
                   `apply_spd` for the paper's sensitivity-ranked plan.
        comm       sync-point comm policy: a CommPolicy for per-block
                   control, or a level string ("exact" | "quant8" |
                   "quant4") applied uniformly to every kept sync;
                   `comm_logits` sets the logits all-gather level for
                   the string form.  When given (even "exact") it
                   replaces any policy already attached to `plan`;
                   None leaves the plan's policy in place.  See
                   docs/comm.md and `apply_comm_policy` for the
                   sensitivity-tiered assignment.
        params     canonical param tree (e.g. from training); a fresh
                   `init_model(PRNGKey(seed))` when omitted.
        page_size/num_pages select the paged KV cache for `serve()` /
        `generate()`; dense per-slot caches otherwise.
        spec       `repro.spec.SpecConfig(k=, draft=)` turns on
                   self-speculative decoding: the draft shares these
                   weights under the preset's aggressive CommPolicy,
                   the exact model verifies k drafts per step (greedy
                   stays token-identical; sampling stays distribution-
                   preserving).  The "tiered" preset needs calibration
                   data — use `enable_spec` instead of `load(spec=)`.
        dp_replicas  data parallelism OVER the TP groups (docs/
                   cluster.md): `serve()`/`generate()` then run through
                   a ClusterRouter over this many replicas — each its
                   own Scheduler (own KV pool / prefix cache / draft
                   state) sharing the loaded engine and weights.
        router     cluster routing policy name when dp_replicas > 1
                   (`repro.cluster.route_policy_names()`): "round-robin"
                   | "least-outstanding" | "prefix-affinity".
        obs        a `repro.obs.Recorder` to instrument every scheduler,
                   router, page pool, and drafter this LLM builds
                   (metrics + request-lifecycle tracing — docs/
                   observability.md).  Default: the zero-overhead null
                   recorder; observability never changes tokens.
        """
        import jax
        from repro.configs import get_config
        from repro.core import model as M

        cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
        if dtype is not None:
            cfg = replace(cfg, dtype=dtype)
        if plan is None:
            k = (int(round(cfg.n_layers * spd))
                 if cfg.spd_applicable else 0)
            plan = SPDPlanConfig.first_k(cfg.n_layers, k)
        elif len(plan.drop_mask) != cfg.n_layers:
            raise ValueError(f"plan covers {len(plan.drop_mask)} layers, "
                             f"model has {cfg.n_layers}")
        if comm is not None or comm_logits != "exact":
            # an explicit comm (even "exact") replaces any policy the
            # plan already carries; comm=None + comm_logits quantizes
            # only the logits gather
            plan = plan.with_comm(
                _resolve_comm(comm, cfg.n_layers, comm_logits))
        from repro.parallel.backend import resolve_backend
        resolve_backend(engine)       # fail fast on unknown engine names
        if dp_replicas < 1:
            from repro.runtime.elastic import ClusterConfigError
            raise ClusterConfigError(
                f"dp_replicas must be >= 1, got {dp_replicas}")
        from repro.cluster.router import make_policy
        make_policy(router)           # fail fast on unknown policy names
        canonical = (params if params is not None
                     else jax.device_put(
                         M.init_model(jax.random.PRNGKey(seed), cfg),
                         _host()))
        cache = CacheConfig(cache_len=cache_len, max_batch=max_batch,
                            page_size=page_size, num_pages=num_pages,
                            prefill_chunk=prefill_chunk)
        llm = cls(cfg, plan, engine, None, None, canonical, cache,
                  mesh=mesh, tp=tp, dp=dp, q_chunk=q_chunk,
                  dp_replicas=dp_replicas, router=router, obs=obs)
        llm._build_engine()
        if spec is not None:
            llm.enable_spec(spec)
        return llm

    def _make_engine(self, plan=None):
        """Fresh engine for `plan` (default: the current serving plan):
        the engine kind resolves through the backend registry
        (repro.parallel.backend), so a newly registered backend is
        loadable here with zero facade changes."""
        from repro.parallel.backend import make_backend
        from repro.runtime.engines import Engine

        plan = plan if plan is not None else self.plan
        backend = make_backend(self.engine_kind, self.cfg, plan,
                               tp=self.tp, dp=self.dp, mesh=self.mesh)
        # backends that build a device mesh share it with later engines
        # (the draft engine must live on the same devices)
        self.mesh = getattr(backend, "mesh", self.mesh)
        return Engine(self.cfg, plan, backend, q_chunk=self.q_chunk)

    def _build_engine(self):
        """(Re)build the engine for `self.plan` and place canonical
        params into its native layout."""
        self.engine = self._make_engine()
        self.params = self._place(self.canonical, padded=False)
        self._sched = None
        if self.spec is not None:
            # the draft placement restacks on ITS plan's segmentation;
            # rebuild it whenever the canonical weights may have moved
            self._build_spec()

    def _place(self, tree, *, padded: bool, engine=None):
        """Canonical (or already-padded) params -> the backend-native
        layout of `engine` (default: the serving engine).  The backend
        carries the plan it was built with, so placement and compiled
        steps can never disagree on segmentation; the draft engine
        places the SAME canonical tensors under its own plan — zero
        extra trained weights, just a second layout."""
        import jax
        from repro.core import model as M

        backend = (engine if engine is not None else self.engine).backend
        with jax.default_device(_host()):
            pt = tree if padded else M.pad_model(tree, self.cfg, self.tp)
            stacked = M.stack_segments(pt, self.cfg, backend.plan)
        return backend.place_params(stacked)

    # ---------------- speculative decoding ----------------

    def enable_spec(self, spec, calib_batches=None, *, sensitivity=None,
                    ranking=None, calib_prompts=None,
                    calib_target: float = 0.45,
                    force_calibration: bool = False):
        """Turn on self-speculative decoding (or switch its config).

        The "tiered" draft preset reuses Algorithm-1's ISB/SB/ESB tiers,
        which need the block sensitivity profile: pass `calib_batches`
        to run the sweep here, or a precomputed `sensitivity`/`ranking`
        pair.

        The "calibrated" preset goes further: it SEARCHES draft
        CommPolicies (uniform drop/quant ladders, plus the sensitivity
        tier mixes when a profile is available) and picks the cheapest
        one whose MEASURED acceptance on held-out prompts clears
        `calib_target` (repro.spec.calibrate).  Prompts come from
        `calib_prompts` (token sequences) or are sliced out of
        `calib_batches`; results are cached per (arch, engine, tp) —
        `force_calibration` re-measures.  The winning
        `CalibrationResult` lands on `self.spec_calibration`.

        Drops any cached scheduler (its draft state is per-scheduler).
        Returns self for chaining."""
        from repro.spec import SpecConfig, SpecError, derive_draft_plan

        if not isinstance(spec, SpecConfig):
            raise TypeError(f"spec must be a repro.spec.SpecConfig, "
                            f"got {spec!r}")
        needs_tiers = spec.draft in ("tiered", "calibrated")
        if (needs_tiers and sensitivity is None
                and calib_batches is not None):
            from repro.core.spd import sweep_sensitivity
            res, _ = sweep_sensitivity(self.cfg, self.canonical,
                                       calib_batches, self.tp,
                                       q_chunk=self.q_chunk)
            sensitivity, ranking = res.sensitivity, res.ranking
        policy = None
        if spec.draft == "calibrated":
            from repro.spec import calibrate_draft
            prompts = calib_prompts
            if prompts is None and calib_batches is not None:
                prompts = self._calib_prompts(calib_batches)
            if prompts is None or not len(prompts):
                raise SpecError(
                    'draft="calibrated" needs held-out prompts: pass '
                    "calib_prompts=[token seqs] or calib_batches to "
                    "enable_spec")
            cal = calibrate_draft(self, prompts, k=spec.k,
                                  target=calib_target,
                                  sensitivity=sensitivity,
                                  force=force_calibration)
            self.spec_calibration = cal
            policy = cal.policy
        self.spec = spec
        self.draft_plan = derive_draft_plan(self.cfg, spec,
                                            sensitivity=sensitivity,
                                            ranking=ranking,
                                            policy=policy)
        self._build_spec()
        return self

    def _calib_prompts(self, calib_batches, *, n: int = 3) -> list:
        """Held-out prompts for draft calibration, sliced from ppl
        calibration batches: the first row of each of the first `n`
        batches, trimmed so prompt + measured decode fit the cache."""
        lim = max(4, min(16, self.cache.cache_len // 4))
        out = []
        for b in calib_batches[:n]:
            arr = np.asarray(b, np.int32)
            row = arr.reshape(-1, arr.shape[-1])[0] if arr.ndim > 1 else arr
            out.append(row[:lim])
        return out

    def disable_spec(self):
        """Back to plain decoding (drops the cached scheduler)."""
        self.spec = None
        self.draft_plan = self.draft_engine = self.draft_params = None
        self._sched = None

    def _build_spec(self):
        """(Re)build the draft engine and re-place the canonical weights
        under the draft plan's segmentation."""
        self.draft_engine = self._make_engine(self.draft_plan)
        self.draft_params = self._place(self.canonical, padded=False,
                                        engine=self.draft_engine)
        self._sched = None

    def _spec_state(self, cache: CacheConfig):
        """Fresh per-scheduler SpecState (each scheduler owns its draft
        KV cache), or None when speculation is off."""
        if self.spec is None:
            return None
        from repro.spec import Drafter, SpecState
        drafter = Drafter(self.draft_engine, self.draft_params,
                          cache.max_batch, cache.cache_len,
                          prefill_chunk=cache.prefill_chunk)
        return SpecState(k=self.spec.k, drafter=drafter,
                         adaptive=self.spec.adaptive,
                         k_min=self.spec.k_min, k_max=self.spec.k_max,
                         tree_width=self.spec.tree_width)

    # ---------------- serving ----------------

    def serve(self, **overrides):
        """A scheduler on this model: a plain `Scheduler`, or — when
        `dp_replicas > 1` — a `repro.cluster.ClusterRouter` over that
        many replicas (same surface: submit/step/run/cancel/completed;
        docs/cluster.md).  Without overrides, returns the (cached)
        scheduler `generate` uses; with overrides (any CacheConfig
        field, plus `dp_replicas` / `router`) builds a fresh one."""
        if overrides:
            import dataclasses
            n = overrides.pop("dp_replicas", self.dp_replicas)
            policy = overrides.pop("router", self.router_policy)
            cc = dataclasses.replace(self.cache, **overrides)
            if n > 1:
                return self.make_cluster(n, policy=policy, cache=cc)
            return Scheduler(self.engine, self.params, cc,
                             spec=self._spec_state(cc), obs=self.obs)
        if self._sched is None:
            self._sched = (
                self.make_cluster() if self.dp_replicas > 1
                else Scheduler(self.engine, self.params, self.cache,
                               spec=self._spec_state(self.cache),
                               obs=self.obs))
        return self._sched

    # ---------------- cluster serving (docs/cluster.md) ----------------

    def replica_factory(self, cache: Optional[CacheConfig] = None):
        """`rid -> Replica` over this model's engine + placed params —
        what `make_cluster` builds from and what the cluster
        `ElasticScaler` scales up with.  Each replica gets its OWN
        `Scheduler` (own KV pool, prefix cache, and draft state); the
        compiled engine steps and the weights are shared, which is the
        honest single-host simulation of weight-replicated DP (a real
        fleet would `device_put` the same canonical tree per replica
        mesh — runtime/elastic.py's re-shard path)."""
        from repro.cluster import Replica

        cc = cache or self.cache

        def factory(rid: int) -> "Replica":
            return Replica(
                rid, Scheduler(self.engine, self.params, cc,
                               spec=self._spec_state(cc), obs=self.obs),
                comm=getattr(self.plan, "comm", None))
        return factory

    def make_cluster(self, n: Optional[int] = None, *, policy=None,
                     cache: Optional[CacheConfig] = None,
                     warmup: bool = True):
        """A `ClusterRouter` over `n` replicas of this model (default:
        the `dp_replicas`/`router` this LLM was loaded with)."""
        from repro.cluster import ClusterConfigError, ClusterRouter

        n = n if n is not None else self.dp_replicas
        if n < 1:
            raise ClusterConfigError(f"need >= 1 replica, got {n}")
        factory = self.replica_factory(cache)
        return ClusterRouter([factory(rid) for rid in range(n)],
                             policy=policy or self.router_policy,
                             warmup=warmup, obs=self.obs)

    def _submit(self, prompts, sampling) -> List[Request]:
        prompts = _as_prompts(prompts)
        sps = _per_request(sampling, len(prompts))
        sched = self.serve()
        reqs = []
        for p, sp in zip(prompts, sps):
            req = Request(uid=self._next_uid, prompt=p, max_new=sp.max_new,
                          sampling=sp)
            self._next_uid -= 1
            reqs.append(req)
        for req in reqs:              # all-or-nothing: validate the whole
            sched.validate(req)       # batch before enqueueing any of it
        stamp = getattr(sched, "note_submit", None)   # ClusterRouter's
        for req in reqs:              # replicas stamp at routed enqueue
            if stamp is not None:
                stamp(req)
            sched.queue.append(req)   # already validated above
        return reqs

    def generate(self, prompts, sampling: Optional[SamplingParams] = None,
                 max_steps: int = 100_000) -> List[RequestOutput]:
        """Run `prompts` to completion; results in submission order.

        `sampling` is one SamplingParams for all prompts or a list with
        one per prompt (default greedy)."""
        reqs = self._submit(prompts, sampling)
        sched = self.serve()
        steps = 0
        try:
            while any(not r.done for r in reqs) and steps < max_steps:
                if not sched.step():
                    break
                steps += 1
        finally:
            # withdraw this batch from the long-lived scheduler on ANY
            # exit (including engine errors / interrupts): finished
            # requests would otherwise accumulate in `completed`,
            # unfinished ones would keep occupying the queue/slots
            sched.cancel(reqs)
        if any(not r.done for r in reqs):
            raise RuntimeError(
                f"generate did not converge in {steps} steps "
                f"({sum(r.done for r in reqs)}/{len(reqs)} done)")
        return [RequestOutput(index=i,
                              prompt_token_ids=[int(t) for t in r.prompt],
                              token_ids=list(r.out),
                              finish_reason=r.finish_reason,
                              n_preempted=r.n_preempted)
                for i, r in enumerate(reqs)]

    def generate_stream(self, prompts,
                        sampling: Optional[SamplingParams] = None,
                        max_steps: int = 100_000) -> Iterator[StreamEvent]:
        """Like `generate` but yields each token as it is produced
        (admission token included; preemption-recomputed tokens are not
        re-emitted)."""
        reqs = self._submit(prompts, sampling)
        sched = self.serve()
        emitted = [0] * len(reqs)

        def drain():
            for i, r in enumerate(reqs):
                while emitted[i] < len(r.out):
                    tok = r.out[emitted[i]]
                    emitted[i] += 1
                    last = r.done and emitted[i] == len(r.out)
                    yield StreamEvent(
                        index=i, token_id=int(tok), done=last,
                        finish_reason=r.finish_reason if last else None)

        steps = 0
        try:
            while any(not r.done for r in reqs) and steps < max_steps:
                if not sched.step():
                    break
                steps += 1
                yield from drain()
            yield from drain()
            if any(not r.done for r in reqs):
                raise RuntimeError(
                    f"stream did not converge in {steps} steps")
        finally:
            # runs on normal completion AND when the caller abandons the
            # generator (GeneratorExit): unfinished requests must not
            # keep occupying the shared scheduler's queue/slots
            sched.cancel(reqs)

    # ---------------- the paper's SPD pipeline ----------------

    def apply_spd(self, calib_batches, *, n_spd: int, tau1: float,
                  tau2: float, lr: float = 5e-5, epochs: int = 10,
                  strategies=("ZS", "B2B", "HG"),
                  q_chunk: Optional[int] = None):
        """Run the full Algorithm-1 pipeline (sensitivity sweep ->
        ISB/SB/ESB tiering -> zero-shot drop / block-to-block
        distillation / head grouping) on this model's canonical params,
        then redeploy the result onto the engine in place.

        Returns the `SPDReport`.  The model's plan, engine, and placed
        params are replaced; any cached scheduler is dropped (its caches
        no longer match the new plan)."""
        from repro.core import spd as SPD

        padded, plan, report = SPD.apply_spd(
            self.cfg, self.canonical, calib_batches, self.tp,
            n_spd=n_spd, tau1=tau1, tau2=tau2, lr=lr, epochs=epochs,
            strategies=strategies, q_chunk=q_chunk or self.q_chunk)
        self.plan = plan
        self.engine = self._make_engine()
        # distilled SPD weights are TP-degree-specific padded tensors —
        # place them directly, do NOT re-pad canonical weights
        self.params = self._place(padded, padded=True)
        self._sched = None
        return report

    # ---------------- sync-point comm policy ----------------

    def apply_comm_policy(self, calib_batches, *, n_spd: int, tau1: float,
                          tau2: float, sb_level: str = "quant8",
                          esb_level: str = "exact", logits: str = "exact",
                          q_chunk: Optional[int] = None):
        """Sensitivity-aware per-block comm policy (docs/comm.md): run
        the Algorithm-1 sensitivity sweep, then give each block the
        cheapest sync it can afford — ISB blocks (within the `n_spd`
        budget) DROP the attention sync, SB blocks keep it at
        `sb_level` (int8 by default), ESB blocks at `esb_level` — and
        run the logits all-gather at `logits`.  Zero-shot: no
        distillation, canonical weights are re-placed under the new
        plan+policy.

        Returns the SensitivityResult; `self.plan.comm` holds the
        assigned CommPolicy afterwards."""
        from repro.core import spd as SPD

        plan, res = SPD.assign_comm_policy(
            self.cfg, self.canonical, calib_batches, self.tp,
            n_spd=n_spd, tau1=tau1, tau2=tau2, sb_level=sb_level,
            esb_level=esb_level, logits=logits,
            q_chunk=q_chunk or self.q_chunk)
        self.plan = plan
        self._build_engine()
        return res

    def set_comm_policy(self, comm, *, logits: str = "exact"):
        """Attach a CommPolicy (or uniform level string) to the current
        plan and rebuild the engine in place (params re-placed — the
        comm-refined segmentation restacks them)."""
        policy = _resolve_comm(comm, self.cfg.n_layers, logits)
        self.plan = self.plan.with_comm(policy)
        self._build_engine()
