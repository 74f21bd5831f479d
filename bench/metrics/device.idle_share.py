"""Device: 1 - (union of operation intervals / traced window) on the
first chip, in %."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0 \
            or not run.trace["busy_s"]:
        return None
    busy = run.trace["busy_s"]
    first = sorted(busy, key=lambda p: int(p.rsplit(":", 1)[-1]))[0]
    return 100.0 * (1.0 - busy[first] / run.trace["window_s"])
