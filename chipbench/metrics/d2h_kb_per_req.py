"""Bytes the serve path copied from device to host over the window
(``SchedulerStats.d2h_bytes``: padded memory, register files, Tag and
random-access addresses, before any host-side slicing) per request
served, in KiB."""
from chipbench.serve_path import delta


def read(run):
    copied, served = delta(run, "d2h_bytes"), len(run.served())
    return copied / served / 1024 if copied is not None and served else None
