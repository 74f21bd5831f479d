"""Kernels (the XLA fusions of the decode program): its roofline share,
in %: the least time the chip could take for the decode work of the
traced part, max(FLOPs / peak FLOP/s, needed bytes / peak HBM bytes/s)
per chip, over the decode program's device seconds on the first chip.
Needed bytes are the chip's weight shard plus the keys and values of
the real context lengths, whatever implements the step.  The traced
steps' mean least time counts once per decode call the trace holds."""
from bench.lib import flops as F


def read(run):
    if not run.trace:
        return None
    prog = run.trace["programs"].get("decode")
    steps = [s for s in run.traced_steps() if s.rows]
    if not prog or not steps or prog["device_s"] <= 0:
        return None
    best = 0.0
    for s in steps:
        ctx = [s.ctx_sum / s.rows] * s.rows
        fl = F.decode_flops(run.arch, ctx) / run.tp
        by = F.decode_bytes_per_chip(run.arch, run.tp, ctx)
        best += max(fl / run.peaks["bf16_flops_per_s"],
                    by / run.peaks["hbm_bytes_per_s"])
    return 100.0 * best / len(steps) * prog["calls"] / prog["device_s"]
