"""Median time to first token, in ms, over every request due in the
window: open loop from the time it was due, closed loop from its send;
one still waiting at the window's end enters at its wait so far."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.ttfts(), 50)
    return None if v is None else 1e3 * v
