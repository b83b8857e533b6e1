"""Required model FLOPs of all prefill and decode work in the traced window, over its seconds times the chip's bf16 peak."""
from bench import readers

UNIT = "%"


def read(ctx):
    return readers.mfu(ctx)
