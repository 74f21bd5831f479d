"""Scheduler: requests decoded per `Scheduler.step()` over `max_batch`,
in %, averaged over the window's steps."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.rows for s in steps) / (len(steps) * run.max_batch)
