"""Least time the live-token paged decode attention of the traced window could take on the chip (memory-bound: live K/V bytes over peak bandwidth), over the paged decode kernel's measured device time."""
from bench import readers

UNIT = "%"


def read(ctx):
    return readers.paged_decode_roofline(ctx)
