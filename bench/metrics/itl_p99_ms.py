"""99th percentile, in ms, of the same token gaps as itl_p50_ms: how long
a stream freezes while other requests' prompts are prefilled.  A gap
whose step admits a request also holds that admission's whole prefill
(`Scheduler._admit` runs before the decode), and such gaps form a group
of their own, well above the decode gaps.  The 99th percentile lies
inside that group as long as more than 1% of gaps hold an admission
(about 7% in this cell), wherever the decode step's time moves the
boundary between the groups; a window holds thousands of gaps, so
dozens lie beyond it."""
from bench.lib.harness import percentile


def read(run):
    v = percentile(run.token_gaps(), 99)
    return None if v is None else 1e3 * v
