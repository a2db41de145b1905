"""Share of the HBM roofline that the decode attention split tiles reach
(``dec_attn`` executables): their architectural bytes at the chip's HBM
peak over their device time in the traced window, in %
(``chipbench/decode_trace.py``)."""
from chipbench.decode_trace import roofline


def read(run):
    return roofline(run, ("attn",))
