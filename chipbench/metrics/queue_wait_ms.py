"""Mean time a request waits in the scheduler's queue, from entering it
to a serve cycle taking it, over the window, in ms: the program's own
counters (``SchedulerStats.queue_wait_s`` over ``.taken``)."""
from chipbench.serve_path import delta


def read(run):
    wait, taken = delta(run, "queue_wait_s"), delta(run, "taken")
    return wait / taken * 1e3 if wait is not None and taken else None
