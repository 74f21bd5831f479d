"""Serving driver: batched prefill+decode with continuous batching,
built on the `repro.api` facade (LLM + SamplingParams + the unified
Scheduler).

Dense (fixed per-slot caches):
``python -m repro.launch.serve --arch smollm-360m-reduced --tp 2 --dp 2
--requests 8 --spd 0.5``

Paged KV cache (block-pool allocator + page-table scheduler, see
docs/serving.md): add ``--page-size 16 --num-pages 48`` — admission is
then limited by free pages instead of slots, and pool exhaustion
preempts and requeues the latest-admitted request.  ``--prefill-chunk C``
switches prompt prefill to fixed-size chunks (one compilation instead of
one per power-of-two bucket) on EITHER cache layout.

Sampling: greedy by default; ``--temperature/--top-k/--top-p
--sample-seed`` select the jitted sampling path (per-request
deterministic).

Sync-point comm policy (docs/comm.md): ``--comm quant8`` runs every
kept sync point (the all-reduces SPD did not drop) through the two-hop
int8 quantized psum; ``--comm quant4`` uses int4; ``--comm-logits``
sets the final logits all-gather level independently.  Composes with
``--spd``: a dropped block's surviving MLP sync is still quantized.

Cluster serving (docs/cluster.md): ``--replicas 2 --router
prefix-affinity`` fronts N weight-shared replicas (each its own
scheduler, KV pool, and prefix cache) with the cluster router —
admission is load-balanced by the chosen policy and the report gains a
per-replica utilization/routing block.  Greedy outputs are identical to
``--replicas 1``: routing picks WHERE a request runs, never perturbs
per-replica numerics.

Self-speculative decoding (docs/speculative.md): ``--spec-k 4
--spec-draft all-drop`` drafts k tokens per step with the SAME weights
under an all-dropped comm plan and verifies them with the exact model
in one multi-token forward — greedy output is token-identical to plain
decoding; the report gains acceptance-rate and tokens/step fields.
(The "tiered" draft preset needs calibration data; use
``LLM.enable_spec`` from Python.)

Observability (docs/observability.md): ``--metrics-json PATH`` writes
the run's metric snapshot (TTFT/TPOT/queue-wait histograms, SPD
drop/quant gauges, comm hidden/exposed time) as a flat dict plus a
Prometheus text exposition; ``--trace PATH`` writes a Chrome/Perfetto
trace (load it at https://ui.perfetto.dev) with per-slot request
lifecycle, scheduler step, spec round, cluster, and comm-ledger tracks.
Either flag turns the instrumentation on; greedy outputs stay
bit-identical with it on or off.
"""
import argparse
import json
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--spd", type=float, default=0.0)
    # any parallel-backend registry name ("sim", "shard", "overlap", ...);
    # not argparse choices= because the registry lives behind the jax
    # import, which must wait for XLA_FLAGS — LLM.load fails fast with
    # the registered names on a typo
    ap.add_argument("--engine", default="shard")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page; with --num-pages selects "
                         "the paged cache (0 = dense)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pages in the shared pool; small values force "
                         "preemption-by-eviction")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill size, dense or paged (0 = "
                         "power-of-two buckets)")
    ap.add_argument("--comm", choices=["exact", "quant8", "quant4"],
                    default="exact",
                    help="quantization level for every kept sync point "
                         "(per-block policies: repro.api.CommPolicy)")
    ap.add_argument("--comm-logits", choices=["exact", "quant8", "quant4"],
                    default="exact",
                    help="quantization level for the logits all-gather")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: tokens drafted per "
                         "verify round (0 = off); with --spec-adaptive "
                         "this is each request's STARTING budget")
    ap.add_argument("--spec-draft",
                    choices=["all-drop", "drop+quant4", "calibrated"],
                    default="all-drop",
                    help="draft comm preset (same weights, cheaper "
                         "syncs); 'calibrated' searches drop/quant "
                         "policies for the cheapest one clearing the "
                         "acceptance target on synthetic held-out "
                         "prompts (see docs/speculative.md)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="per-request adaptive draft budget: k grows on "
                         "fully accepted rounds (cap --spec-k-max) and "
                         "shrinks on rejection streaks (floor 1)")
    ap.add_argument("--spec-k-max", type=int, default=0,
                    help="adaptive budget ceiling (0 = --spec-k)")
    ap.add_argument("--spec-tree-width", type=int, default=1,
                    help="tree speculation: also verify the draft's "
                         "top-2..top-W first-position candidates as "
                         "depth-1 branches in the same forward (1 = "
                         "chain)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="DP-over-TP cluster serving: number of "
                         "weight-shared replicas behind the cluster "
                         "router (1 = plain single scheduler)")
    ap.add_argument("--router", default="least-outstanding",
                    help="cluster routing policy (round-robin | "
                         "least-outstanding | prefix-affinity)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default); > 0 samples")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics snapshot (flat dict + "
                         "Prometheus text) to this path "
                         "(docs/observability.md)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace_event JSON of "
                         "the run (request lifecycle, scheduler steps, "
                         "spec rounds, comm ledger) to this path")
    args = ap.parse_args()

    n_dev = args.tp * args.dp
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}")

    import numpy as np
    from repro.api import LLM, SamplingParams, SpecConfig
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    # observability (docs/observability.md): an isolated registry +
    # wall-clock tracer, wired through every scheduler / pool / router
    # the facade builds.  obs=None keeps the zero-overhead null recorder.
    obs = None
    if args.metrics_json or args.trace:
        from repro.obs import MetricsRegistry, Recorder, Tracer
        obs = Recorder(MetricsRegistry(), Tracer())

    paged = args.page_size > 0 and args.num_pages > 0
    spec = None
    if args.spec_k > 0:
        spec = SpecConfig(
            k=args.spec_k, draft=args.spec_draft,
            adaptive=args.spec_adaptive,
            k_max=(args.spec_k_max or None) if args.spec_adaptive
            else None, tree_width=args.spec_tree_width)
    llm = LLM.load(
        args.arch, tp=args.tp, dp=args.dp, engine=args.engine,
        spd=args.spd, dtype=args.dtype, seed=args.seed,
        comm=args.comm, comm_logits=args.comm_logits,
        cache_len=args.cache_len, max_batch=args.max_batch,
        page_size=args.page_size if paged else None,
        num_pages=args.num_pages if paged else None,
        prefill_chunk=args.prefill_chunk or None, q_chunk=64,
        dp_replicas=args.replicas, router=args.router,
        spec=spec if args.spec_draft != "calibrated" else None, obs=obs)
    if spec is not None and args.spec_draft == "calibrated":
        # held-out synthetic prompts (disjoint seed from the serving
        # prompts below) drive the cheapest-qualifying policy search
        crng = np.random.default_rng(args.seed + 1_000_003)
        calib = [crng.integers(0, llm.cfg.vocab_size, 12).astype(np.int32)
                 for _ in range(3)]
        llm.enable_spec(spec, calib_prompts=calib)

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, llm.cfg.vocab_size,
                            int(rng.integers(4, 24))).astype(np.int32)
               for _ in range(args.requests)]
    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.sample_seed, max_new=args.max_new)
    if obs is not None:
        # comm entries record at TRACE time (first compilation), so the
        # ledger must be open around generate's first forward passes
        from repro.parallel.collectives import (LatencyModel,
                                                collective_ledger)
        lat = LatencyModel()
        with collective_ledger(latency=lat, tp=args.tp) as comm_entries:
            outs = llm.generate(prompts, sampling)
        comm_agg = obs.record_comm(comm_entries, lat, tp=args.tp,
                                   overlap=(args.engine == "overlap"))
    else:
        outs = llm.generate(prompts, sampling)
    sched = llm.serve()
    out = {
        "completed": sum(o.finished for o in outs),
        "outputs": {o.index: o.token_ids[:8] for o in outs},
    }
    # replicas > 1: sched is a repro.cluster.ClusterRouter — per-replica
    # stats come from its stats() block, aggregates from its replicas
    cluster = args.replicas > 1
    scheds = ([rep.sched for rep in sched.replicas.values()]
              if cluster else [sched])
    if args.comm != "exact" or args.comm_logits != "exact":
        out["comm"] = {"blocks": args.comm, "logits": args.comm_logits}
    if args.spec_k > 0:
        drafted = sum(s.spec_drafted for s in scheds)
        out["spec"] = {"k": args.spec_k, "draft": args.spec_draft,
                       "acceptance": round(
                           sum(s.spec_accepted for s in scheds)
                           / max(drafted, 1), 4),
                       "tokens_per_step": round(
                           sum(s.spec_committed for s in scheds)
                           / max(sum(s.spec_row_rounds
                                     for s in scheds), 1), 4)}
        if args.spec_adaptive:
            out["spec"]["adaptive"] = {"k_max": args.spec_k_max
                                       or args.spec_k}
        if args.spec_tree_width > 1:
            out["spec"]["tree"] = {
                "width": args.spec_tree_width,
                "alt_commits": sum(s.spec_alt_commits for s in scheds)}
        if llm.spec_calibration is not None:
            cal = llm.spec_calibration
            out["spec"]["calibrated"] = {
                "policy": cal.name,
                "calib_acceptance": round(cal.acceptance, 4),
                "trials": len(cal.trials)}
    if paged:
        out["paged"] = {"page_size": args.page_size,
                        "num_pages": args.num_pages,
                        "preemptions": sum(s.n_preemptions
                                           for s in scheds),
                        "free_pages": sum(s.pool.num_free
                                          for s in scheds),
                        "pool_high_water": max(s.pool.high_water
                                               for s in scheds),
                        "prefix_hits": sum(s.kv.prefix_hits
                                           for s in scheds)}
    if cluster:
        out["cluster"] = sched.stats()

    if obs is not None:
        # SPD plan shape as gauges, so the Prometheus snapshot carries
        # the drop/quant configuration next to the comm-time counters
        plan = llm.plan
        qm = plan.qmodes or ("exact",) * len(plan.drop_mask)
        obs.gauge("spd_dropped_syncs", plan.n_dropped)
        obs.gauge("spd_quant_syncs",
                  sum(1 for d, m in zip(plan.drop_mask, qm)
                      if not d and m != "exact"))
        obs.gauge("spd_drop_ratio", plan.fraction)
        out["obs"] = {"comm": {k: round(v, 2) if isinstance(v, float)
                               else v for k, v in comm_agg.items()},
                      "tracks": obs.tracer.tracks()}
        if args.metrics_json:
            with open(args.metrics_json, "w") as f:
                json.dump({"metrics": obs.snapshot(),
                           "prometheus": obs.metrics.to_prometheus()},
                          f, indent=1)
            out["obs"]["metrics_json"] = args.metrics_json
        if args.trace:
            obs.tracer.save(args.trace)
            out["obs"]["trace"] = args.trace
    print(json.dumps(out))


if __name__ == "__main__":
    main()
