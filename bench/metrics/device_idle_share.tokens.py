"""Share of the traced window in which no operation ran on the device."""
from bench import readers

UNIT = "%"


def read(ctx):
    return readers.idle_share(ctx)
