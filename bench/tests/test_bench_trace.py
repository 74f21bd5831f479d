"""The reduction from a profiler trace to per-layer numbers, on a small
fixture whose answers are worked out by hand, and the reader on a trace
recorded here."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.lib import trace as TR  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_small.json")


@pytest.fixture
def small():
    import json
    with open(FIXTURE) as f:
        return TR.Trace.from_json(json.load(f))


def test_programs_are_labelled_from_label_spans(small):
    assert TR.label_programs(small) == {"111": "decode", "222": "prefill"}


def test_reduce_busy_programs_allreduce(small):
    r = TR.reduce(small, {"111": "decode", "222": "prefill"}, 0, 16000)
    assert r["window_s"] == pytest.approx(16e-6)
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(10e-6)
    assert r["busy_s"]["/device:TPU:1"] == pytest.approx(1e-6)
    assert r["programs"]["decode"] == {"device_s": pytest.approx(7e-6),
                                       "calls": 2}
    assert r["programs"]["prefill"] == {"device_s": pytest.approx(3e-6),
                                        "calls": 1}
    assert r["allreduce_s"] == {"decode": pytest.approx(2e-6)}
    names = [n for n, _ in r["device_ops"]]
    assert names == ["decode:fusion.1", "prefill:convolution.4",
                     "decode:all-reduce.2", "decode:fusion.3"]
    assert r["device_ops"][0][1] == pytest.approx(4e-6)


def test_idle_gaps_are_named_by_host_span(small):
    r = TR.reduce(small, {}, 0, 16000)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(3e-6)]
    assert [g[0] for g in gaps[1:]] == ["bench.step"] * 3
    assert sum(g[1] for g in gaps) == pytest.approx(6e-6)
    # unlabelled programs count as "other"
    assert set(r["programs"]) == {"other"}


def test_idle_gaps_take_the_innermost_program_span():
    """With the program's spans on the host plane, a gap inside the
    scheduler's host phase is named by it, not by the step around it;
    the label and traced-window spans name nothing."""
    tr = TR.Trace(
        modules={"/device:TPU:0": [("jit_local(1)", 0, 1000),
                                   ("jit_local(1)", 3000, 4000)]},
        ops={"/device:TPU:0": [("%fusion.1 = f()", 0, 1000),
                               ("%fusion.1 = f()", 3000, 4000)]},
        spans=[("bench.traced", 0, 4000), ("bench.label.decode", 0, 4000),
               ("bench.step", 900, 3100), ("scheduler.step", 950, 3050),
               ("scheduler.prep", 1000, 2900), ("pool.table", 2000, 2100)])
    assert TR.reduce(tr, {}, 0, 4000)["idle_gaps"] == [
        ["scheduler.prep", pytest.approx(2e-6)]]


def test_window_clips_events(small):
    r = TR.reduce(small, {"111": "decode"}, 2000, 13000)
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(7e-6)
    assert r["programs"]["decode"]["device_s"] == pytest.approx(4e-6)


def test_opcode_and_names():
    op = "%all-reduce-start.7 = (bf16[4]{0:T(4)}, u32[]{:S(2)}) " \
         "all-reduce-start(bf16[4]{0} %p), to_apply=%sum"
    assert TR.opcode(op) == "all-reduce-start"
    assert TR.short_name(op) == "all-reduce-start.7"
    assert TR.program_id("jit_local(9157453366283328995)") == \
        "9157453366283328995"
    assert TR.program_id("no id") is None


def test_reads_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(4)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.wait"):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    tr = TR.load(TR.find_xplane(str(tmp_path)))
    names = [s[0] for s in tr.spans]
    assert names == ["bench.step", "bench.wait"]
    assert all(e > s for _, s, e in tr.spans)


def test_load_keeps_the_programs_spans(tmp_path):
    """The Recorder's profiler mirror (`Tracer(profiler=True)`) puts the
    scheduler's and the pool's spans on the host plane; `load` keeps
    them beside the benchmark's, and nothing else of that plane."""
    import jax

    from repro.obs import MetricsRegistry, Recorder, Tracer

    rec = Recorder(MetricsRegistry(), Tracer(profiler=True))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        with rec.span("scheduler", "prep"):
            with rec.span("pool", "cow"):
                time.sleep(0.001)
    with jax.profiler.TraceAnnotation("other.thing"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    tr = TR.load(TR.find_xplane(str(tmp_path)))
    assert [s[0] for s in tr.spans] == ["bench.step", "scheduler.prep",
                                        "pool.cow"]
