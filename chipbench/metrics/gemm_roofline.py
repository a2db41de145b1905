"""Share of the HBM roofline that the decode GEMV tiles reach (``dec_gemv_d``
and ``dec_gemv_ff`` executables: q, k, v, o, gate, up and down): their
architectural bytes at the chip's HBM peak over their device time in the
traced window, in % (``chipbench/decode_trace.py``)."""
from chipbench.decode_trace import roofline


def read(run):
    return roofline(run, ("gemv_d", "gemv_ff"))
