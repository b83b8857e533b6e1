"""Tokens produced by decode steps in the window over decode steps times slots: the share of slot-steps that decoded a live candidate."""
from bench import readers

UNIT = "%"


def read(ctx):
    c = ctx["counters"]
    if not c["steps"]:
        return None
    return 100.0 * readers.decode_tokens(ctx) / (c["steps"] * c["slots"])
