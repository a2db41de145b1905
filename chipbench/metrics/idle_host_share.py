"""Share of the device's idle time in the traced window during which some
host thread is inside a span of host work on the serve path
(``sched.group``, ``sched.promote``, ``sched.dispatch``, ``sched.fetch``,
``sched.resolve``, ``engine.lower``).  In %."""
from chipbench import serve_path


def read(run):
    got = serve_path.spans(run)
    if got is None or got["idle_host_s"] is None or not got["idle_s"]:
        return None
    return got["idle_host_s"] / got["idle_s"] * 100.0
