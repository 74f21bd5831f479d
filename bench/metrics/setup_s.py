"""Seconds from process start to the window's start: weights, load,
compilation or the compile cache, warm-up, and the traffic's ramp."""


def read(run):
    return run.setup_s
