"""Bytes the serve path copied from host to device over the window
(``SchedulerStats.h2d_bytes``: padded VM images, stacked fused images,
donated copies) per request served, in KiB."""
from chipbench.serve_path import delta


def read(run):
    copied, served = delta(run, "h2d_bytes"), len(run.served())
    return copied / served / 1024 if copied is not None and served else None
