"""90th percentile of the time to first token, in ms, over the same set
as ttft_p50_ms."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.ttfts(), 90)
    return None if v is None else 1e3 * v
