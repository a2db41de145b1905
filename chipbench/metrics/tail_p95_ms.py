"""95th percentile of an open-loop window's latencies, as the client sees
them: each from the request's due time to the moment its client saw the
result, host stalls included, in ms.  The same arithmetic as
``latency_p50_ms``; it stands beside that end-to-end metric unbounded,
because the host's stalls move it too far between runs for a bound
(PERF.md)."""
from chipbench.drive import latency_ms


def read(run):
    return latency_ms(run.window.records, 95)
