"""Scheduler: mean duration, in ms, of the program's own
`scheduler.admit` spans that ended in the window: how long one admission
(prefill, first-token pull, pool insert) holds the batch.  Reads the
spans of the program's Recorder (`run.program_spans`,
bench/lib/spans.py); None in a run that attached none."""
from bench.lib import spans as SP


def read(run):
    spans = getattr(run, "program_spans", None)
    return SP.admit_ms(spans, run.w0, run.w1) if spans else None
