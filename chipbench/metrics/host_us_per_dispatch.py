"""Host time of the serve worker per dispatch in the traced window: the
self time of the spans ``sched.group``, ``sched.dispatch``,
``sched.fetch`` and ``sched.resolve`` over the number of
``sched.dispatch`` spans, in microseconds."""
from chipbench import serve_path

HOST = ("sched.group", "sched.dispatch", "sched.fetch", "sched.resolve")


def read(run):
    got = serve_path.spans(run)
    if got is None or "sched.dispatch" not in got["spans"]:
        return None
    times = got["spans"]
    n = times["sched.dispatch"]["count"]
    spent = sum(times[k]["self_s"] for k in HOST if k in times)
    return spent / n * 1e6 if n else None
