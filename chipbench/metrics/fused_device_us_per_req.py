"""Device time of the fused tier's executables (``core/engine.py``) in
the traced window, per request the fused tier served, in microseconds."""


def read(run):
    return run.device_us_per_request("fused")
