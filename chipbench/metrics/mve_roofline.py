"""Share of the HBM roofline that the MVE executables reach: the
architectural bytes of the served programs' vector loads and stores
(``reference.access_bytes``; the same whichever tier ran them) at the
chip's peak HBM bandwidth, over the device time of every VM and fused
executable in the traced window.  In %."""


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace["module_s"]["vm"] + run.trace["module_s"]["fused"]
    served = run.served()
    if seconds <= 0 or not served:
        return None
    total = sum(run.access_bytes(r.request) for r in served)
    return total / run.peaks["hbm_bytes_per_s"] / seconds * 100.0
