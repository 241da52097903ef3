"""Model operations of the window's work over device busy time at peak."""
from perfbench import readers


def read(r):
    return readers.mfu(r, "busy")
