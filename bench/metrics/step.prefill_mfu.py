"""Model step: model FLOPs of the prompts admitted in the traced part of
the window over (device seconds of the prefill programs x chips x peak
bf16 FLOP/s), in %."""
from bench.lib import flops as F


def read(run):
    if not run.trace:
        return None
    prog = run.trace["programs"].get("prefill")
    prompts = [p for s in run.traced_steps() for p in s.prefills]
    if not prog or not prompts or prog["device_s"] <= 0:
        return None
    work = sum(F.prefill_flops(run.arch, p) for p in prompts)
    return 100.0 * work / (prog["device_s"] * run.chips
                           * run.peaks["bf16_flops_per_s"])
