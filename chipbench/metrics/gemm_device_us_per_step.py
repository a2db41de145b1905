"""Device time of the decode GEMV tiles (``dec_gemv_d`` and ``dec_gemv_ff``
executables) in the traced window per decode step answered, in
microseconds."""
from chipbench.decode_trace import device_us_per_step


def read(run):
    return device_us_per_step(run, ("gemv_d", "gemv_ff"))
