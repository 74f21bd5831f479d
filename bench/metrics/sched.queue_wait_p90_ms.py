"""Scheduler: 90th percentile, in ms, of the time from a request's due
time to the start of the step that took it off the queue (one still
queued at the window's end enters at its wait so far)."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.queue_waits(), 90)
    return None if v is None else 1e3 * v
