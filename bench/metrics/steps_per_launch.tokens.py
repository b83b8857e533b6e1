"""Device decode steps per macro-step launch in the window."""
UNIT = "steps"


def read(ctx):
    c = ctx["counters"]
    return c["steps"] / c["launches"] if c["launches"] else None
