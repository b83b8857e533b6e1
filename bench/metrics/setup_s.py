"""Seconds from the start of the run to the end of the warm-up: loading, weights, engine, compiles or cache loads, and the warm-up requests. The lead-in that fills the slots before the window opens is not counted."""
UNIT = "s"


def read(ctx):
    return ctx["setup_s"]
