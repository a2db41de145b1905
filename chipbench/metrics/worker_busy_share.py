"""Share of the traced window in which the serve worker is inside one of
its spans other than those that wait for work (``sched.idle``,
``sched.window``): grouping, promotion, dispatch, sync, fetch, resolve.
In %."""
from chipbench import serve_path


def read(run):
    got = serve_path.spans(run)
    if got is None or got["worker_busy_s"] is None:
        return None
    return got["worker_busy_s"] / got["window_s"] * 100.0
