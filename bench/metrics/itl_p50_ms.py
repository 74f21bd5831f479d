"""Median, in ms, of every gap between consecutive output tokens of a
request, where the later token came in the window: the streaming cadence
while nothing is admitted.  Most steps admit no request (about 93% of
gaps in this cell), so the median is always a plain decode gap: one
decode step plus the host's round trip between steps."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.token_gaps(), 50)
    return None if v is None else 1e3 * v
