"""Device time of the VM datapath's executables (``core/vm.py``) in the
traced window, per request the VM tier served, in microseconds."""


def read(run):
    return run.device_us_per_request("vm")
