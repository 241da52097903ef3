"""The paged decode kernel's share of its roofline, in the throughput cell."""
from perfbench import readers


def read(r):
    return readers.paged_decode_roofline(r)
