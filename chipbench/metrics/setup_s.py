"""Seconds from the process's start to the opening of the window:
imports, traffic generation, the VM prewarm, promotion, and every
executable the window uses, loaded or compiled and run once."""


def read(run):
    return run.setup_s
