"""Mean host time between decode launches in the traced window, in ms: from the end of one launch's readback to the dispatch of the next, over the gaps in which the engine had work (the engine's own counters). The traced window and not the whole one: the profiler's stop after it holds the host for seconds."""
UNIT = "ms"


def read(ctx):
    c = ctx.get("trace_counters")
    s = {} if c is None else c["sched"]
    n = s.get("launch_gaps")
    return s["launch_gap_ns"] / n / 1e6 if n else None
