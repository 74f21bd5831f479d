"""Page pool: pages in use (`Scheduler.metrics()["pool_pages_used"]`)
over `num_pages`, in %, averaged over the window's steps."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.pool_used for s in steps) / (len(steps)
                                                      * run.num_pages)
