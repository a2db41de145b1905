"""Requests answered with a result over the seconds of the window, which
a closed loop ends with its last whole round."""
from chipbench.drive import served


def read(run):
    w = run.window
    return len(served(w.records)) / (w.close - w.start)
