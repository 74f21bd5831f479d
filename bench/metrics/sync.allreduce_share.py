"""Syncs: device time of the all-reduce operations inside the decode
program over the decode program's device time, in %, on the first chip,
over the traced part of the window.  Cells with more than one chip."""


def read(run):
    if not run.trace or run.tp < 2:
        return None
    prog = run.trace["programs"].get("decode")
    if not prog or prog["device_s"] <= 0:
        return None
    return 100.0 * run.trace["allreduce_s"].get("decode", 0.0) \
        / prog["device_s"]
