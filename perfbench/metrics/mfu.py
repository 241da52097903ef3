"""Model operations of the window's work over the window at bf16 peak."""
from perfbench import readers


def read(r):
    return readers.mfu(r, "window")
