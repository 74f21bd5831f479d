"""Model step: decode model FLOPs (2 x matmul weights per row decoded,
plus attention over each row's real context) over (decode device
seconds x chips x peak bf16 FLOP/s), in %, over the traced part: the
mean work of the traced steps times the decode calls the trace holds."""
from bench.lib import flops as F


def read(run):
    if not run.trace:
        return None
    prog = run.trace["programs"].get("decode")
    steps = [s for s in run.traced_steps() if s.rows]
    if not prog or not steps or prog["device_s"] <= 0:
        return None
    work = sum(F.decode_flops(run.arch, [s.ctx_sum / s.rows] * s.rows)
               for s in steps) / len(steps) * prog["calls"]
    return 100.0 * work / (prog["device_s"] * run.chips
                           * run.peaks["bf16_flops_per_s"])
