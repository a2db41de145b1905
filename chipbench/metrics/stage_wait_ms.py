"""Mean time a decode stage takes from its first tile's submission to its
last tile's answer, over the window, in ms: the runner's own counters
(``repro.nn.qwen2.DecodeStats``: ``stage_wait_s`` over ``stages``)."""
from chipbench.decode_trace import counters


def read(run):
    dec = counters(run)
    if dec is None:
        return None
    n = dec["after"]["stages"] - dec["before"]["stages"]
    waited = dec["after"]["stage_wait_s"] - dec["before"]["stage_wait_s"]
    return waited / n * 1e3 if n else None
