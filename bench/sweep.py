"""Find an open-loop cell's knee once: the highest arrival rate the
server sustains without a growing queue.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates 1.5 2 2.5 3 3.5

One process loads the cell's model and warms it once, then offers the
cell's traffic at each rate in turn (ramp, then the window), and prints
one JSON line per rate: tokens/s, TTFT percentiles, queue depth at the
window's start and end, occupancy and preemptions.  The rate is then
fixed in the cell's traffic file at about four fifths of the knee.
Benchmark runs never run this."""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import run as R
    from bench.lib import decoder as D
    from bench.lib import harness as H
    from bench.lib import spec
    from bench.lib import traffic as T
    from bench.lib.peaks import peaks
    from repro.api import LLM
    from repro.config.base import ModelConfig

    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        R.fail(f"needs {cell.chips} TPU chips, JAX has {devices}", 3)
    devices = devices[:cell.chips]
    R.compile_cache()
    cfg, mix, load = cell.config, cell.traffic, cell.config["load"]
    if mix["loop"] != "open":
        R.fail("the knee is a property of open-loop cells")
    arch = cell.model_module().arch(cfg)
    params = D.init_params(args.seed, arch, devices,
                           dtype=jax.numpy.dtype(cfg["torch_dtype"]))
    server = H.Server(cell, params, ModelConfig, LLM)
    buckets = H.table_buckets(mix, load["page_size"],
                              load["cache_len"] // load["page_size"],
                              load["cache_len"])
    server.warm(buckets, arch.vocab)
    sched = server.sched
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        run = H.Run(cell=cell, arch=arch, tp=load["tp"],
                    max_batch=load["max_batch"],
                    num_pages=load["num_pages"], seconds=args.seconds,
                    peaks=peaks(devices[0].device_kind), chips=len(devices))
        pre = sched.n_preemptions
        H.serve_window(run, sched, T.generate(m, args.seed, args.seconds,
                                              arch.vocab, load["cache_len"]),
                       m)
        steps = run.window_steps()
        q = [s.queue for s in steps]
        tt = run.ttfts()
        gaps = run.token_gaps()
        R.emit({"rate_per_s": rate,
                "out_tok_s": run.window_tokens() / (run.w1 - run.w0),
                "ttft_p50_ms": 1e3 * H.percentile(tt, 50),
                "ttft_p90_ms": 1e3 * H.percentile(tt, 90),
                "itl_p50_ms": 1e3 * H.percentile(gaps, 50),
                "itl_p99_ms": 1e3 * H.percentile(gaps, 99),
                "queue_first_quarter": float(np.mean(q[:len(q) // 4])),
                "queue_last_quarter": float(np.mean(q[-len(q) // 4:])),
                "occupancy": float(np.mean([s.rows for s in steps]))
                / load["max_batch"],
                "preemptions": sched.n_preemptions - pre,
                "steps": len(steps),
                "step_ms_mean": 1e3 * float(np.mean([s.t1 - s.t0
                                                     for s in steps]))})
        sched.cancel([r.handle for r in run.requests])
        sched.completed.clear()
    print(json.dumps({"done": True, "memory_peak_bytes": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices)}))


if __name__ == "__main__":
    main()
