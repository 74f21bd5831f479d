"""95th percentile, in ms, of every gap between consecutive output tokens
of a request, where the later token came in the window."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.token_gaps(), 95)
    return None if v is None else 1e3 * v
