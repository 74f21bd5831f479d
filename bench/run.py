"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits are found
by name from BENCHMARK.json (bench/lib/spec.py).  The run draws weights
and requests from --seed, loads the program (`LLM.load` on the `shard`
backend), warms every program shape the cell's traffic reaches, sends
the traffic through `Scheduler.submit`/`Scheduler.step` for --seconds,
then checks what it served against the plain float32 reference.

Earlier lines of stdout are JSON records (set-up parts, compile-cache
use, counts); the last line is the result:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "check"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read from a profiler trace of part of
the window, from the program's own spans over the whole window (a
`repro.obs.Recorder` attached to the scheduler for the window alone) and
from the harness's records.  The numbers `correct` is
judged by end stderr and the result line, each beside its limit.

Exits non-zero, printing no result, when JAX's first device is not a
TPU, when the machine has fewer chips than the cell asks for, or when
the program (`src/repro`) is not in the checkout."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def process_age() -> float:
    """Seconds since this process started, from /proc (0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = process_age()


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def fail(msg: str, code: int = 2):
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import spec

    try:
        cell = spec.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(f"workload {args.workload!r}: {e}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the program (src/repro) is not in this checkout")

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is {d0.platform!r} "
             f"({d0.device_kind})", 3)
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX has "
             f"{len(devices)}", 3)
    from bench.lib.peaks import peaks

    run_one(cell, args, devices[:cell.chips], peaks(d0.device_kind),
            len(devices))


def run_one(cell, args, devices, pk: dict, n_devices: int,
            control: bool = False) -> dict:
    """Everything of a run after the look for the chips: `devices` the
    cell's own, `pk` their published peaks.  Returns the result line.
    `control` (bench/control.py, never a benchmark run) also judges the
    fp8 control, put in the program's place, by the cell's own limits."""
    import jax

    from bench.lib import check as C
    from bench.lib import decoder as D
    from bench.lib import harness as H
    from bench.lib import spans as SP
    from bench.lib import spec
    from bench.lib import trace as TR
    from bench.lib import traffic as T
    from repro.api import LLM
    from repro.config.base import ModelConfig
    from repro.obs import MetricsRegistry, Recorder, Tracer

    d0 = devices[0]
    cache_dir = compile_cache()
    counter = H.CompileCounter()
    cfg, mix, load = cell.config, cell.traffic, cell.config["load"]
    model = cell.model_module()
    arch = model.arch(cfg)
    parts = {"process_start_s": AGE_AT_START}
    emit({"phase": "start", "workload": cell.name, "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace,
          "compile_cache": cache_dir,
          "device": {"kind": d0.device_kind, "count": n_devices}})

    t = time.perf_counter()
    params = D.init_params(args.seed, arch, devices,
                           dtype=jax.numpy.dtype(cfg["torch_dtype"]))
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    server = H.Server(cell, params, ModelConfig, LLM)
    parts["load_s"] = time.perf_counter() - t
    pages_per_slot = load["cache_len"] // load["page_size"]
    buckets = H.table_buckets(mix, load["page_size"], pages_per_slot,
                              load["cache_len"])
    t = time.perf_counter()
    server.warm(buckets, arch.vocab)
    parts["warm_s"] = time.perf_counter() - t
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    labels = {}
    if args.trace:
        t = time.perf_counter()
        H.label_session(server, buckets, os.path.join(tmp, "labels"))
        labels = TR.label_programs(TR.load(TR.find_xplane(
            os.path.join(tmp, "labels"))))
        parts["label_s"] = time.perf_counter() - t
    parts.update({"compile_before_window": counter.snapshot(),
                  "decode_buckets": buckets})

    reqs = T.generate(mix, args.seed, args.seconds, arch.vocab,
                      load["cache_len"])
    run = H.Run(cell=cell, arch=arch, tp=load["tp"],
                max_batch=load["max_batch"], num_pages=load["num_pages"],
                seconds=args.seconds, peaks=pk, chips=len(devices))
    # a traced run records the program's own spans over the window only:
    # warm-up and labelling ran with no recorder attached
    rec = Recorder(MetricsRegistry(), Tracer(profiler=True)) \
        if args.trace else None
    server.sched.set_obs(rec)
    try:
        H.serve_window(run, server.sched, reqs, mix,
                       trace_dir=os.path.join(tmp, "window") if tmp
                       else None,
                       trace_s=float(mix.get("trace_s", 5.0)),
                       counter=counter)
    finally:
        server.sched.set_obs(None)
    if rec:
        run.program_spans = SP.from_tracer(
            rec.tracer.to_dict()["traceEvents"], rec.tracer.origin)
    run.setup_s = run.w0 - T_START + AGE_AT_START
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    if args.trace and load["tp"] > 1:
        run.allreduces_per_decode = decode_allreduces(server,
                                                      buckets[-1])
    emit({"phase": "window", "setup_s": run.setup_s, "parts": parts,
          "window_s": run.w1 - run.w0, "steps": len(run.window_steps()),
          "compiles_in_window": run.compiles_in_window,
          "late_p50_s": H.percentile(run.lateness, 50),
          "late_max_s": max(run.lateness) if run.lateness else 0.0,
          "preemptions": server.sched.n_preemptions,
          "requests_sent": len(run.requests),
          "token_gaps": run.gap_summary(),
          "memory_peak_bytes": mem_peak})
    server.free()
    del server
    gc.collect()

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": n_devices, "memory_peak_bytes": int(mem_peak)}
    breakdown = None
    if args.trace:
        tr = TR.load(TR.find_xplane(os.path.join(tmp, "window")))
        spans = [s for s in tr.spans if s[0] == "bench.traced"]
        lo, hi = (spans[0][1], spans[0][2]) if spans else (0, 0)
        run.trace = TR.reduce(tr, labels, lo, hi)
        shutil.rmtree(tmp, ignore_errors=True)
        busy = list(run.trace["busy_s"].values())
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}

    # correctness: the reference over a sample of what was served
    t = time.perf_counter()
    finished = [r for r in run.requests if r.handle.done]
    chk = cell.check
    picked = C.sample(finished, args.seed, chk["sample_tokens"],
                      chk["max_requests"], chk["limits"]["min_requests"])
    seqs, n_served = C.sequences(picked)
    mask = drop_mask(arch, load)
    other = None
    if control and picked:
        other = [r.argmax for r in D.token_logits(
            params, arch, mask, load["tp"], seqs, n_served,
            mode="fp8", device=devices[0])]
    readings = D.token_logits(params, arch, mask, load["tp"], seqs,
                              n_served, other=other,
                              device=devices[0]) if picked else []
    result = C.judge(readings, chk["limits"], len(picked))
    if other is not None:
        ctl = C.judge(C.control_readings(readings), chk["limits"],
                      len(picked))
        emit({"phase": "control", "seed": args.seed,
              "correct": C.passed(ctl), "check": ctl,
              "program_check": result,
              "tokens": int(sum(len(r.gap) for r in readings))})
        for name, v in ctl.items():
            print(f"control {name} {v['value']!r} limit {v['limit']!r}",
                  file=sys.stderr)
    correct = C.passed(result)
    emit({"phase": "check", "seconds": time.perf_counter() - t,
          "requests": len(picked), "tokens": int(sum(n_served)),
          "finished": len(finished)})

    metrics = spec.read_metrics(cell.per_layer if args.trace
                                else cell.end_to_end, run)
    due = run.due_in_window()
    out = {"correct": bool(correct), "attempted": len(due), "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = result
    for name, v in result.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    emit(out)
    return out


def compile_cache() -> str:
    """The program's persistent compilation cache (`.jax_cache/` in the
    checkout, or $JAX_COMPILATION_CACHE_DIR), keeping every program, so
    that only a checkout's first run of a cell compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def drop_mask(arch, load) -> list:
    """The SPD plan `LLM.load(spd=...)` builds: the first round(L * spd)
    blocks drop their attention sync."""
    k = int(round(arch.layers * load["spd"]))
    return [i < k for i in range(arch.layers)]


def decode_allreduces(server, width: int) -> int:
    """All-reduces the compiled greedy paged-decode step executes per call
    (trip-count aware), from its HLO."""
    import jax.numpy as jnp

    from bench.lib.hlo import collective_counts

    s = server.sched
    step = server.llm.engine._decode_paged(False)
    txt = step.lower(server.llm.params, jnp.asarray(s.cur),
                     jnp.asarray(s.pos),
                     jnp.full((s.max_batch, width), -1, jnp.int32),
                     s.kv.pcaches).compile().as_text()
    return collective_counts(txt)["all-reduce"]["executed"]


if __name__ == "__main__":
    main()
