"""Model step: device time per call of the paged decode program, in ms,
on the first chip, over the traced part of the window."""


def read(run):
    if not run.trace:
        return None
    prog = run.trace["programs"].get("decode")
    if not prog or not prog["calls"]:
        return None
    return 1e3 * prog["device_s"] / prog["calls"]
