"""Share of the traced window with no device operation (latency cell)."""
from perfbench import readers


def read(r):
    return readers.idle_share(r)
