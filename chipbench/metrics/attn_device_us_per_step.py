"""Device time of the decode attention split tiles (``dec_attn``
executables) in the traced window per decode step answered, in
microseconds."""
from chipbench.decode_trace import device_us_per_step


def read(run):
    return device_us_per_step(run, ("attn",))
