"""Requests per executable launch over the window, from the scheduler's
own counters (``SchedulerStats.requests`` and ``.dispatches``)."""


def read(run):
    w = run.window
    dispatches = w.stats_after["dispatches"] - w.stats_before["dispatches"]
    requests = w.stats_after["requests"] - w.stats_before["requests"]
    return requests / dispatches if dispatches else None
