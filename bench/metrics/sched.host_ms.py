"""Scheduler: median, over the window's decode steps, of the program's
own `scheduler.prep` + `scheduler.commit` spans, in ms: the host work the
synchronous loop makes the device wait for.  Reads the spans of the
program's Recorder (`run.program_spans`, bench/lib/spans.py); None in a
run that attached none."""
from bench.lib import spans as SP


def read(run):
    spans = getattr(run, "program_spans", None)
    return SP.host_ms(spans, run.w0, run.w1) if spans else None
