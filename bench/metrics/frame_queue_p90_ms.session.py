"""90th percentile over served frames of the wait from a frame's due time
to the start of its admission, in ms (the program's per-frame
``queue_s``); None where the program does not report it."""
from bench.stats import percentile


def read(ctx):
    q = [r.stats["queue_s"] * 1e3 for r in ctx["served"] if "queue_s" in r.stats]
    return percentile(q, 90) if q else None
