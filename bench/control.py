"""Readings for the limits of `correct`: the program's widest logit gap
and the fp8 control's, on several seeds of one cell, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Each seed is a whole run of the cell (its own weights, traffic and
window, at the cell's own sizes); after the usual check the reference
also reads, at every served position, the token an fp8 forward (both
operands of every matmul rounded to float8_e4m3) puts first, and judges
those tokens, put in the place of the served ones, by the cell's own
limits: a `control` record whose `correct` has to be false.  A limit
belongs above the largest program reading and below the smallest
control reading (PERF.md gives both).  Benchmark runs never run this."""
from __future__ import annotations

import argparse
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import run as R
    from bench.lib import spec
    from bench.lib.peaks import peaks

    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        R.fail(f"needs {cell.chips} TPU chips, JAX has {devices}", 3)
    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        R.run_one(cell, ns, devices[:cell.chips],
                  peaks(devices[0].device_kind), len(devices),
                  control=True)


if __name__ == "__main__":
    main()
