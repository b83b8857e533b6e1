"""Mean wait from submit to first admission of the requests first admitted in the window, in ms (the engine's own counters); its base, the count of those requests, goes to standard error."""
import sys

UNIT = "ms"


def read(ctx):
    s = ctx["counters"]["sched"]
    n = s.get("first_admissions")
    if not n:
        return None
    print(f"queue_wait_ms.tokens: over {n} first admissions",
          file=sys.stderr)
    return s["queue_wait_ns"] / n / 1e6
