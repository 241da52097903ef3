"""Live decode slots per in-flight decode step over the window."""
from perfbench import readers


def read(r):
    return readers.slots_per_step(r)
