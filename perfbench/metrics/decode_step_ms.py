"""The traced window over the in-flight decode steps taken in it."""
from perfbench import readers


def read(r):
    return readers.decode_step_ms(r)
