"""Unit tests for `repro.obs`: metrics-registry semantics, the
Chrome/Perfetto tracer under an injected virtual clock, comm-ledger
re-emission, and the scheduler instrumentation — including the two
contracts everything else rides on:

  * deterministic snapshots: the same workload under the same
    VirtualClock produces byte-identical trace events;
  * on/off parity: greedy token streams are bit-identical with a live
    Recorder attached or the default NULL_RECORDER (observability can
    never perturb serving).
"""
import json

import numpy as np
import pytest

from test_scheduler_soak import FakeDrafter, FakeEngine, V, reference_stream

from repro.api.scheduler import CacheConfig, Request, Scheduler
from repro.obs import (DEFAULT_BUCKETS, MetricsRegistry, NULL_RECORDER,
                       Recorder, Tracer, VirtualClock, default_registry,
                       emit_comm, set_default_registry)
from repro.parallel.collectives import (CommEntry, LatencyModel,
                                        collective_ledger, comm_context,
                                        comm_phase, log_collective)


def mk_requests(n, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, V, int(rng.integers(2, 10))
                                        ).astype(np.int32),
                    max_new=max_new)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_and_gauge_series():
    reg = MetricsRegistry()
    reg.inc("reqs_total")
    reg.inc("reqs_total", 2.0)
    reg.inc("reqs_total", reason="stop")
    reg.set("depth", 7, queue="main")
    reg.set("depth", 3, queue="main")            # last write wins
    snap = reg.snapshot()
    assert snap["reqs_total"] == 3.0
    assert snap['reqs_total{reason="stop"}'] == 1.0
    assert snap['depth{queue="main"}'] == 3.0
    assert reg.get("reqs_total").get(reason="stop") == 1.0
    with pytest.raises(ValueError):
        reg.inc("reqs_total", -1.0)              # counters are monotonic


def test_histogram_buckets_sum_count():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    key = ()
    assert h.cumulative(key) == [1, 2, 3, 4]
    assert h.count() == 4 and h.sum() == pytest.approx(55.55)
    snap = reg.snapshot()
    assert snap['lat_bucket{le="0.1"}'] == 1
    assert snap['lat_bucket{le="10"}'] == 3      # cumulative
    assert snap['lat_bucket{le="+Inf"}'] == 4    # 50.0 lands past the top
    assert snap["lat_count"] == 4
    assert snap["lat_sum"] == pytest.approx(55.55)


def test_metric_type_and_bucket_conflicts():
    reg = MetricsRegistry()
    reg.inc("m")
    with pytest.raises(TypeError):
        reg.set("m", 1.0)                        # counter vs gauge
    reg.histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 3.0))   # layout is fixed
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(2.0, 1.0))  # must increase
    assert reg.observe("auto", 0.01) is None     # auto-registers defaults
    assert reg.get("auto").buckets == DEFAULT_BUCKETS


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests accepted").inc(3, kind="a")
    reg.observe("lat", 0.3)
    text = reg.to_prometheus()
    assert "# HELP reqs_total requests accepted" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{kind="a"} 3' in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 1' in text


def test_default_registry_swap_roundtrip():
    mine = MetricsRegistry()
    prev = set_default_registry(mine)
    try:
        assert default_registry() is mine
        Recorder().inc("x")                      # metrics=None binds it
        assert mine.snapshot()["x"] == 1.0
    finally:
        set_default_registry(prev)
    assert default_registry() is prev


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def _virtual_trace():
    tr = Tracer(clock=VirtualClock(start=5.0, tick=0.5))
    with tr.span("sched", "step", round=1) as s:
        s["active"] = 2
    tr.instant("cluster", "scale_up", {"rid": 1})
    tr.counter("sched", "active_slots", 2)
    return tr


def test_tracer_virtual_clock_deterministic():
    a, b = _virtual_trace(), _virtual_trace()
    assert a.events == b.events                  # byte-identical snapshot
    assert a.tracks() == ["sched", "cluster"]
    x = [e for e in a.events if e["ph"] == "X"][0]
    # t0 = 5.0; span enter reads 5.5 -> ts 0.5s, exit reads 6.0 -> 0.5s
    assert x["ts"] == pytest.approx(0.5e6) and x["dur"] == pytest.approx(
        0.5e6)
    assert x["args"] == {"round": 1, "active": 2}


def test_tracer_chrome_schema(tmp_path):
    tr = _virtual_trace()
    d = tr.to_dict()
    assert set(d) == {"traceEvents", "displayTimeUnit"}
    names = [e["name"] for e in d["traceEvents"] if e["ph"] == "M"]
    assert names.count("thread_name") == 2       # one per track
    for e in d["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    p = tmp_path / "trace.json"
    tr.save(str(p))
    assert json.loads(p.read_text())["traceEvents"] == d["traceEvents"]


# ---------------------------------------------------------------------------
# Comm-ledger re-emission
# ---------------------------------------------------------------------------


def test_emit_comm_hidden_exposed_split_and_metrics():
    lat, tp = LatencyModel(), 4
    def priced(op, nbytes, overlappable, block=-1, phase=""):
        return CommEntry(op, "tp", nbytes, overlappable,
                         lat.collective_us(op, nbytes, tp), lat.launch_us,
                         block, phase)
    entries = [
        priced("all-reduce", 4096, True, 3, "prefill"),   # kept exact sync
        priced("reduce-scatter", 2048, True, 5, "decode"),  # quant 2-hop
        priced("all-gather", 1024, True, 5, "decode"),
        priced("all-gather", 8192, False),                # logits gather
    ]
    tr = Tracer(clock=VirtualClock())
    reg = MetricsRegistry()
    agg = emit_comm(tr, entries, lat, tp=tp, overlap=True, metrics=reg)
    assert agg["entries"] == 4
    assert agg["total_us"] == pytest.approx(sum(e.est_us for e in entries))
    # split_us contract: hidden + exposed == est_us exactly, per entry
    assert agg["hidden_us"] + agg["exposed_us"] == pytest.approx(
        agg["total_us"])
    assert agg["hidden_us"] > 0.0
    assert agg["kept_sync_us"] == pytest.approx(
        sum(e.est_us for e in entries if e.overlappable))
    assert agg["quant_bytes"] == 2048 + 1024     # overlappable non-AR
    snap = reg.snapshot()
    assert snap['comm_entries_total{op="all-gather"}'] == 2.0
    assert snap["comm_hidden_us_total"] == pytest.approx(agg["hidden_us"])
    assert snap["spd_quant_bytes_total"] == 3072.0
    # slices lie end to end on one "comm" track, phase-suffixed names
    xs = [e for e in tr.events if e["ph"] == "X"]
    assert [e["name"] for e in xs] == [
        "all-reduce[prefill]", "reduce-scatter[decode]",
        "all-gather[decode]", "all-gather"]
    assert xs[0]["args"]["block"] == 3
    cursor = 0.0
    for e in xs:
        assert e["ts"] == pytest.approx(cursor, abs=0.01)
        cursor += e["dur"]


def test_emit_comm_prices_byte_only_entries():
    lat = LatencyModel()
    raw = [CommEntry("all-reduce", "tp", 1 << 20, True)]   # est_us == 0
    agg = emit_comm(Tracer(clock=VirtualClock()), raw, lat, tp=8)
    assert agg["total_us"] == pytest.approx(
        lat.collective_us("all-reduce", 1 << 20, 8))
    # no latency model -> stays pure byte accounting
    agg0 = emit_comm(Tracer(clock=VirtualClock()), raw)
    assert agg0["total_us"] == 0.0


def test_comm_context_labels_ledger_entries():
    with collective_ledger() as led:
        log_collective("all-reduce", "tp", 100)
        with comm_context(block=3, phase="prefill"):
            log_collective("all-reduce", "tp", 100)
            with comm_phase("verify"):           # phase-only override
                log_collective("all-gather", "tp", 50)
            log_collective("all-reduce", "tp", 100)
        log_collective("all-reduce", "tp", 100)
    assert [(e.block, e.phase) for e in led] == [
        (-1, ""), (3, "prefill"), (3, "verify"), (3, "prefill"), (-1, "")]
    # backward compat: pre-PR 6-field positional construction still binds
    e = CommEntry("all-reduce", "tp", 10, True, 1.0, 0.1)
    assert e.block == -1 and e.phase == ""


# ---------------------------------------------------------------------------
# Scheduler instrumentation (FakeEngine: host-side, deterministic)
# ---------------------------------------------------------------------------


def _run(obs=None, n=6, max_new=6, num_pages=8):
    cc = CacheConfig(cache_len=32, max_batch=2, page_size=4,
                     num_pages=num_pages)
    sched = Scheduler(FakeEngine(), None, cc, obs=obs)
    reqs = mk_requests(n, seed=3, max_new=max_new)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched, reqs


def test_scheduler_metrics_and_trace():
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    sched, reqs = _run(obs)
    snap = obs.snapshot()
    n = len(reqs)
    assert snap["requests_submitted_total"] == n
    assert snap["ttft_seconds_count"] == n       # one TTFT per request
    assert snap["tpot_seconds_count"] == n
    assert snap["queue_wait_seconds_count"] >= n  # re-admits re-observe
    assert sum(v for k, v in snap.items()
               if k.startswith("requests_finished_total")) == n
    assert snap["tokens_generated_total"] == sum(len(r.out) for r in reqs)
    if sched.n_preemptions:
        assert snap["preemptions_total"] == sched.n_preemptions
    # pool occupancy gauge + high-water mark moved
    assert snap["pool_pages_used"] >= 0 and sched.pool.high_water > 0
    # trace: scheduler step spans + per-slot queue/serve slices
    tr = obs.tracer
    assert "scheduler" in tr.tracks() and "slot0" in tr.tracks()
    names = [e["name"] for e in tr.events if e["ph"] == "X"]
    steps = names.count("step")
    assert steps > 0
    # one active_slots counter sample per scheduler step
    assert sum(1 for e in tr.events if e["ph"] == "C") == steps
    for want in ("step", "queue", "prefill", "serve"):
        assert want in names
    serve_done = [e for e in tr.events if e["ph"] == "X"
                  and e["name"] == "serve" and "reason" in e.get("args", {})]
    assert len(serve_done) == n                  # one final slice each
    # Scheduler.metrics() bundles native stats + the registry snapshot
    m = sched.metrics()
    assert m["completed"] == n and m["registry"] == snap


def test_scheduler_preemption_instrumented():
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    # max_new larger than the per-slot page budget forces pool pressure
    sched, reqs = _run(obs, n=4, max_new=12, num_pages=6)
    assert sched.n_preemptions > 0               # the scenario preempts
    snap = obs.snapshot()
    assert snap["preemptions_total"] == sched.n_preemptions
    marks = [e for e in obs.tracer.events
             if e["ph"] == "i" and e["name"] == "preempt"]
    assert len(marks) == sched.n_preemptions
    # greedy streams stay exact under instrumentation + preemption
    for r in reqs:
        assert r.out == reference_stream(r.prompt, len(r.out))


def test_obs_on_off_token_parity():
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    on, reqs_on = _run(obs, n=5, max_new=10, num_pages=6)
    off, reqs_off = _run(None, n=5, max_new=10, num_pages=6)
    assert [r.out for r in reqs_on] == [r.out for r in reqs_off]
    assert on.n_preemptions == off.n_preemptions
    assert off.obs is NULL_RECORDER and off.metrics().get("registry") is None


def test_spec_round_instrumentation():
    from repro.spec import SpecState
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    cc = CacheConfig(cache_len=32, max_batch=2, page_size=4, num_pages=12)
    sched = Scheduler(FakeEngine(), None, cc,
                      spec=SpecState(k=3, drafter=FakeDrafter(cc.max_batch)),
                      obs=obs)
    reqs = mk_requests(4, seed=7, max_new=6)
    for r in reqs:
        sched.submit(r)
    sched.run()
    snap = obs.snapshot()
    assert snap["spec_drafted_total"] == sched.spec_drafted
    assert snap["spec_accepted_total"] == sched.spec_accepted
    assert snap["spec_acceptance_ratio_count"] == sched.spec_row_rounds
    names = [e["name"] for e in obs.tracer.events if e["ph"] == "X"]
    assert "draft" in names and "verify" in names
    for r in reqs:                               # committed streams exact
        assert r.out == reference_stream(r.prompt, len(r.out))


def test_adaptive_tree_spec_obs_schema():
    """The adaptive/tree round instrumentation: per-slot `spec_k`
    gauges, the per-request `spec_request_acceptance` histogram (one
    observation per finished request that drafted), the tree
    alt-commit counter, and tree-labeled draft/verify spans."""
    from repro.spec import SpecState
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    cc = CacheConfig(cache_len=32, max_batch=2, page_size=4, num_pages=12)
    sched = Scheduler(FakeEngine(), None, cc,
                      spec=SpecState(k=3, drafter=FakeDrafter(cc.max_batch),
                                     adaptive=True, k_min=1, k_max=4,
                                     tree_width=2),
                      obs=obs)
    reqs = mk_requests(4, seed=7, max_new=8)
    for r in reqs:
        sched.submit(r)
    sched.run()
    snap = obs.snapshot()
    # per-slot adaptive budget gauge, labeled — within the window
    ks = {k: v for k, v in snap.items() if k.startswith('spec_k{')}
    assert ks and all(k.startswith('spec_k{slot="') for k in ks)
    assert all(1 <= v <= 4 for v in ks.values())
    # per-request acceptance histogram: one observation per finished
    # request that drafted, values are ratios in [0, 1]
    drafted = [r for r in reqs if r.n_drafted]
    assert snap["spec_request_acceptance_count"] == len(drafted)
    assert snap["spec_request_acceptance_sum"] == pytest.approx(
        sum(r.n_draft_accepted / r.n_drafted for r in drafted))
    assert snap['spec_request_acceptance_bucket{le="+Inf"}'] == len(drafted)
    # tree recovery counter mirrors the scheduler's native stat
    assert snap.get("spec_tree_alt_commits_total", 0.0) \
        == sched.spec_alt_commits
    # draft/verify spans are tree-labeled
    spans = [e for e in obs.tracer.events
             if e["ph"] == "X" and e["name"] in ("draft", "verify")]
    assert spans and all(e["args"]["tree"] == 2 for e in spans)
    assert sched.metrics()["spec_alt_commits"] == sched.spec_alt_commits
    for r in reqs:
        assert r.out == reference_stream(r.prompt, len(r.out))


def test_null_recorder_is_inert():
    assert not NULL_RECORDER.enabled
    assert NULL_RECORDER.now() == 0.0
    NULL_RECORDER.inc("x")
    NULL_RECORDER.gauge("x", 1)
    NULL_RECORDER.observe("x", 1)
    NULL_RECORDER.instant("t", "n")
    with NULL_RECORDER.span("t", "n") as s:
        s["k"] = "v"                             # writable throwaway dict
    assert NULL_RECORDER.snapshot() == {}
    assert NULL_RECORDER.record_comm([], None) == {}


def test_warmup_is_obs_invisible():
    from repro.cluster import Replica
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    cc = CacheConfig(cache_len=32, max_batch=2, page_size=4, num_pages=12)
    rep = Replica(0, Scheduler(FakeEngine(), None, cc, obs=obs))
    rep.start(warmup=True)
    assert obs.snapshot() == {}                  # throwaway request unseen
    assert obs.tracer.events == []
    assert rep.sched.obs is obs                  # recorder restored
    assert rep.sched.pool.obs is obs
    assert rep.sched.pool.high_water == 0        # canonical restore


# ---------------------------------------------------------------------------
# Host-phase spans and the profiler mirror (Tracer(profiler=True))
# ---------------------------------------------------------------------------


def _span_tree(tracer):
    """[(name, children)] of the top-level complete slices, each child
    named `<track>.<name>`; parents are found by time containment (the
    virtual clock advances on every read, so nesting is strict)."""
    tracks = {e["tid"]: e["args"]["name"] for e in tracer.events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    xs = sorted(((f"{tracks[e['tid']]}.{e['name']}", e["ts"],
                  e["ts"] + e["dur"]) for e in tracer.events
                 if e["ph"] == "X" and not tracks[e["tid"]].startswith(
                     "slot")), key=lambda t: (t[1], -t[2]))
    root = ("root", -1.0, float("inf"), [])
    stack = [root]
    for n, s, e in xs:
        while not (stack[-1][1] <= s and e <= stack[-1][2]):
            stack.pop()
        node = (n, s, e, [])
        stack[-1][3].append(node)
        stack.append(node)
    return root[3]


def test_decode_step_span_tree():
    """Every decode step is step ⊃ {admit*, prep ⊃ {pool.cow, pool.table},
    wait, commit}, with one `admit` per admission (re-admissions after a
    preemption included)."""
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    sched, reqs = _run(obs, n=5, max_new=10, num_pages=6)
    assert sched.n_preemptions > 0
    steps = _span_tree(obs.tracer)
    assert steps and {s[0] for s in steps} == {"scheduler.step"}
    admits = decodes = 0
    for _, _, _, kids in steps:
        names = [k[0] for k in kids]
        n_admit = names.count("scheduler.admit")
        admits += n_admit
        assert names[:n_admit] == ["scheduler.admit"] * n_admit
        if names[n_admit:]:
            decodes += 1
            assert names[n_admit:] == ["scheduler.prep", "scheduler.wait",
                                       "scheduler.commit"]
            prep = kids[n_admit]
            assert [k[0] for k in prep[3]] == ["pool.cow", "pool.table"]
        for k in kids:
            if k[0] != "scheduler.prep":
                assert k[3] == []                # leaves
    assert decodes > 0
    assert admits == len(reqs) + sched.n_preemptions
    assert admits == obs.snapshot()["queue_wait_seconds_count"]


def test_admit_span_bounds_prefill_slice():
    """The slot's prefill slice and the admit span are the same two
    clock reads; the admit span carries the admission's args."""
    obs = Recorder(MetricsRegistry(), Tracer(clock=VirtualClock(tick=1e-3)))
    _run(obs, n=3)
    ev = obs.tracer.events
    admits = [e for e in ev if e["ph"] == "X" and e["name"] == "admit"]
    prefills = [e for e in ev if e["ph"] == "X" and e["name"] == "prefill"]
    assert len(admits) == len(prefills) == 3
    for a, p in zip(admits, prefills):
        assert (a["ts"], a["dur"]) == (p["ts"], p["dur"])
        assert a["args"]["uid"] == p["args"]["uid"]
        assert set(a["args"]) == {"uid", "slot", "tokens", "cached"}


def test_ttft_ends_at_first_token_not_pool_insert():
    """TTFT stops once the first token is on the host; the admit span
    (and the slot's prefill slice) runs on through the pool insert."""
    clock = VirtualClock()
    obs = Recorder(MetricsRegistry(), Tracer(clock=clock))
    cc = CacheConfig(cache_len=32, max_batch=2, page_size=4, num_pages=8)
    sched = Scheduler(FakeEngine(), None, cc, obs=obs)
    insert = sched.kv.insert

    def slow_insert(*a, **k):
        clock.advance(1.0)
        return insert(*a, **k)

    sched.kv.insert = slow_insert
    sched.submit(mk_requests(1, seed=3, max_new=3)[0])
    sched.run()
    snap = obs.snapshot()
    assert snap["ttft_seconds_count"] == 1
    assert snap["ttft_seconds_sum"] == 0.0
    admit, = [e for e in obs.tracer.events
              if e["ph"] == "X" and e["name"] == "admit"]
    assert admit["dur"] >= 1e6                   # us: the insert's second


def test_profiler_recorder_token_parity():
    obs = Recorder(MetricsRegistry(), Tracer(profiler=True))
    on, reqs_on = _run(obs, n=5, max_new=10, num_pages=6)
    off, reqs_off = _run(None, n=5, max_new=10, num_pages=6)
    assert [r.out for r in reqs_on] == [r.out for r in reqs_off]
    assert on.n_preemptions == off.n_preemptions


def test_profiler_session_holds_scheduler_spans(tmp_path):
    """A real jax.profiler session records the mirrored spans on the
    `/host:CPU` plane, nested as the in-memory ones are."""
    import glob

    import jax

    obs = Recorder(MetricsRegistry(), Tracer(profiler=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(obs, n=3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name == "/host:CPU"][0]
    evs = [(e.name, e.start_ns, e.end_ns) for line in host.lines
           for e in line.events
           if e.name.startswith(("scheduler.", "pool."))]
    names = {n for n, _, _ in evs}
    assert names == {"scheduler.step", "scheduler.admit", "scheduler.prep",
                     "scheduler.wait", "scheduler.commit", "pool.cow",
                     "pool.table"}
    n_x = sum(1 for e in obs.tracer.events if e["ph"] == "X"
              and e["name"] in ("step", "admit", "prep", "wait", "commit",
                                "cow", "table"))
    assert len(evs) == n_x                       # every span mirrored once
    steps = [(s, e) for n, s, e in evs if n == "scheduler.step"]
    for n, s, e in evs:
        if n == "scheduler.wait":
            assert any(a <= s and e <= b for a, b in steps)


def test_obs_import_stays_jax_free():
    import subprocess
    import sys
    code = ("import sys; from repro.obs import Recorder, Tracer; "
            "Recorder(tracer=Tracer()); assert 'jax' not in sys.modules; "
            "Tracer(profiler=True); assert 'jax' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
