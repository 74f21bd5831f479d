"""The token-gap metrics on a synthetic run with the cell's two groups of
gaps: plain decode gaps at one step's cadence, and gaps whose step also
admitted a request, spread over the admissions' prefill times."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.lib import harness as H  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.lib import traffic as T  # noqa: E402

DECODE_S = 0.062
N = 2000


def two_group_run(admit_share: float) -> H.Run:
    """One request streams N tokens after its first; a share of the gaps
    close on a step that admitted a request, spread evenly over
    100-160 ms and scattered through the stream, the rest are 62 ms."""
    n_admit = int(round(N * admit_share))
    gaps = np.full(N, DECODE_S)
    at = np.random.default_rng(0).permutation(N)[:n_admit]
    gaps[at] = np.linspace(0.100, 0.160, n_admit)
    times = np.concatenate([[0.0], np.cumsum(gaps)]).tolist()
    admitting = {times[i + 1] for i in at}
    run = H.Run(cell=None, arch=None, tp=1, max_batch=32, num_pages=64,
                seconds=times[-1], peaks={}, chips=1)
    r = T.Request(rid=0, prompt=np.zeros(4, np.int32), max_new=N + 1)
    r.token_times = times
    run.requests = [r]
    run.steps = [H.Step(t0=a, t1=b, rows=1, ctx_sum=0,
                        prefills=[1000] if b in admitting else [],
                        pool_used=0, queue=0)
                 for a, b in zip(times, times[1:])]
    run.w0, run.w1 = 0.0, times[-1]
    return run


@pytest.mark.parametrize("admit_share", [0.07, 0.04, 0.015])
def test_p50_reads_the_decode_group(admit_share):
    run = two_group_run(admit_share)
    got = spec.metric_reader("itl_p50_ms")(run)
    assert got == pytest.approx(1e3 * DECODE_S)


@pytest.mark.parametrize("admit_share", [0.07, 0.04, 0.015])
def test_p99_reads_the_admission_group(admit_share):
    """Wherever more than 1% of gaps hold an admission, the 99th
    percentile lies among them; the 95th leaves them below 5%."""
    run = two_group_run(admit_share)
    got = spec.metric_reader("itl_p99_ms")(run)
    assert 100.0 <= got <= 160.0
    p95 = 1e3 * H.percentile(run.token_gaps(), 95)
    assert (p95 >= 100.0) == (admit_share > 0.05)


def test_gap_summary_counts_the_admitting_gaps():
    run = two_group_run(0.07)
    g = run.gap_summary()
    assert g["n"] == N
    assert g["admit_share"] == pytest.approx(0.07)
    assert g["p50"] == pytest.approx(1e3 * DECODE_S)
    assert g["p99"] == pytest.approx(spec.metric_reader("itl_p99_ms")(run))
    assert [k for k in g if k.startswith("p")] == [
        "p50", "p90", "p95", "p97", "p98", "p99", "p99.5"]
    # gaps that close after the window are not the window's
    run.w1 = run.requests[0].token_times[N // 2]
    assert run.gap_summary()["n"] == N // 2


def test_a_run_without_gaps_reads_nothing():
    run = two_group_run(0.07)
    run.requests = []
    assert spec.metric_reader("itl_p50_ms")(run) is None
    assert spec.metric_reader("itl_p99_ms")(run) is None
    assert run.gap_summary()["admit_share"] is None
