"""Median of an open-loop window's latencies, each from the request's due
time in the schedule to the moment its client saw the result (memory,
registers, Tag) in host memory, in ms.  The program's own time stamps
are not used."""
from chipbench.drive import latency_ms


def read(run):
    return latency_ms(run.window.records, 50)
