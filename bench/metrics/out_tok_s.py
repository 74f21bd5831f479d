"""Output tokens produced in the window over the window's seconds (all
the work and all the time from the first step boundary at the window's
start to the first one past its end)."""


def read(run):
    span = run.w1 - run.w0
    return run.window_tokens() / span if span > 0 else None
