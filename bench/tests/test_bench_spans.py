"""The readers of the program's own spans and named scopes
(bench/lib/spans.py), on a small fixture whose answers are worked out by
hand: a Recorder's in-memory spans, the profiler's copies on the host
plane, and one chip's device trace in which a `while` op encloses
scoped ops."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.lib import spans as SP  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "spans_small.json")


@pytest.fixture
def fx():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture
def spans(fx):
    return SP.from_tracer(fx["tracer"], fx["origin"])


def test_tracer_slices_on_the_host_clock(fx, spans):
    names = [n for n, _, _ in spans]
    assert names[:3] == ["scheduler.step", "scheduler.admit",
                         "slot0.prefill"]
    assert spans[0][1] == pytest.approx(100.0)
    assert spans[0][2] == pytest.approx(100.001)
    assert names.count("scheduler.step") == 4
    assert "pool.cow" in names and "pool.table" in names


def test_host_ms_is_the_median_decode_step_prep_plus_commit(fx, spans):
    lo, hi = fx["window"]
    # steps 1 and 2 decode: 90 + 40 and 80 + 20 us; step 3 only admits
    assert SP.host_ms(spans, lo, hi) == pytest.approx(0.115)
    per = SP.decode_steps(spans, lo, hi)
    assert len(per) == 2
    assert per[0]["pool.cow"] == pytest.approx(20e-6)
    assert per[0]["scheduler.admit"] == pytest.approx(590e-6)
    # step 4 ends after the window; a window with no decode reads None
    assert SP.host_ms(spans, 100.001, hi) == pytest.approx(0.1)
    assert SP.host_ms(spans, 100.01, 100.02) is None


def test_admit_ms_is_the_mean_admission(fx, spans):
    lo, hi = fx["window"]
    assert SP.admit_ms(spans, lo, hi) == pytest.approx((0.59 + 0.09) / 2)
    assert SP.admit_ms(spans, 100.002, 100.01) is None


def test_idle_gaps_take_the_innermost_span_covering_most(fx):
    host = [tuple(s) for s in fx["host_spans"]]
    # inside the page-table upload
    assert SP.innermost(host, 1100, 1400) == "pool.table"
    # mostly in prep, partly in the table: prep covers most of it
    assert SP.innermost(host, 600, 1100) == "scheduler.prep"
    # 30% in wait, 70% in commit
    assert SP.innermost(host, 17700, 18700) == "scheduler.commit"
    # between steps: bench.step alone covers it
    assert SP.innermost(host, 19900, 20000) == "bench.step"
    # no span covers half: the one covering most
    assert SP.innermost(host, 20500, 22000) == "bench.wait"
    assert SP.innermost(host, 40000, 41000) == "host:other"
    skip = SP.innermost(host, 1100, 1400, skip=lambda n: n == "pool.table")
    assert skip == "scheduler.prep"


def test_leaves_leave_out_the_enclosing_while(fx):
    ops = [tuple(o) for o in fx["ops"]]
    names = [SP.short_name(n) for n, _, _ in SP.leaves(ops)]
    assert names.count("while.5") == 0
    assert len(names) == len(ops) - 2
    decode = [o for o in ops if o[2] <= 11000]
    assert sum(e - s for _, s, e in decode) > 10000       # nested twice
    assert sum(e - s for _, s, e in SP.leaves(decode)) == 8500


def test_hlo_scopes_read_op_name_metadata(fx):
    a, b = (SP.hlo_scopes(t) for t in fx["hlo"])
    assert a["fusion.1"].split("/")[-3:] == ["attn", "jit(_take)", "gather"]
    assert a["while.5"] == "jit(decode_paged)/while"
    assert a["gather.9"].endswith("/gather")       # fused instructions too
    assert b["copy.8"] == ""                       # no metadata
    assert "fusion.7" in b and "fusion.7" not in a


def test_attn_share_counts_each_scoped_leaf_once(fx):
    ops = [tuple(o) for o in fx["ops"]]
    mods = [tuple(m) for m in fx["modules"]]
    share = SP.scope_share(ops, mods, fx["hlo"], "decode_paged", "attn",
                           0, 20000)
    # program 111: fusion.1 (2500 ns) + fusion.2 (2000 ns) of 10000 ns;
    # program 333 takes the second module's scopes, where fusion.7
    # (1500 ns) is attn and its while and unnamed copy are not; the
    # prefill program's fusion.1 is no decode time
    assert share == pytest.approx(100.0 * 6000 / 14000)
    # the window holds only the first decode call
    assert SP.scope_share(ops, mods, fx["hlo"], "decode_paged", "attn",
                          0, 15000) == pytest.approx(45.0)
    assert SP.scope_share(ops, mods, fx["hlo"], "decode_paged", "sync",
                          0, 20000) == 0.0
    assert SP.scope_share(ops, mods, [], "decode_paged", "attn",
                          0, 20000) == 0.0
    assert SP.scope_share(ops, mods, fx["hlo"], "verify_paged", "attn",
                          0, 20000) is None


def test_module_key_reads_the_stable_program_name():
    assert SP.module_key("jit_decode_paged(111)") == "decode_paged"
    assert SP.module_key("jit_prefill_chunk(-5)") == "prefill_chunk"
    assert SP.module_key("jit_local") == "local"


@pytest.mark.parametrize("name,want", [("sched.host_ms", 0.115),
                                       ("sched.admit_ms", 0.34)])
def test_span_metric_readers(fx, spans, name, want):
    from types import SimpleNamespace

    from bench.lib import spec

    read = spec.metric_reader(name)
    lo, hi = fx["window"]
    assert read(SimpleNamespace(program_spans=spans, w0=lo, w1=hi)) \
        == pytest.approx(want)
    # a run without the program's spans (the Recorder off, or a program
    # that has none) reads nothing and raises nothing
    assert read(SimpleNamespace(w0=lo, w1=hi)) is None
    assert read(SimpleNamespace(program_spans=[], w0=lo, w1=hi)) is None


def test_attn_share_reader():
    from types import SimpleNamespace

    from bench.lib import spec

    read = spec.metric_reader("kernel.decode_attn_share")
    assert read(SimpleNamespace(trace={"decode_attn_share": 48.5})) == 48.5
    assert read(SimpleNamespace(trace={"programs": {}})) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_cell_reads_the_span_metrics_from_the_programs_recorder(fx, spans):
    """The cell's per-layer entries reach the span readers through
    `Run.program_spans`, which a traced run fills from the Recorder's
    tracer; with no device trace and no steps nothing else reads."""
    from bench.lib import harness as H
    from bench.lib import spec

    cell = spec.cell("qwen3-1.7b.chat-open")
    run = H.Run(cell=cell, arch=None, tp=1, max_batch=32, num_pages=64,
                seconds=1.0, peaks={}, chips=1)
    run.w0, run.w1 = fx["window"]
    assert spec.read_metrics(cell.per_layer, run) == {}
    run.program_spans = spans
    got = spec.read_metrics(cell.per_layer, run)
    assert got == {"sched.host_ms": {"value": pytest.approx(0.115),
                                     "unit": "ms"},
                   "sched.admit_ms": {"value": pytest.approx(0.34),
                                      "unit": "ms"}}
