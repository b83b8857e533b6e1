"""Peak KV pages in use over the pool's pages, in the window."""
UNIT = "%"


def read(ctx):
    kv = ctx["counters"].get("kv")
    return None if kv is None else 100.0 * kv["max_in_use"] / kv["num_pages"]
