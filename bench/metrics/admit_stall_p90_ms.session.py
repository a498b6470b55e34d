"""90th percentile of the blocking admission stall per frame, in ms (the
program's per-frame ``admit_stall_s``)."""
from bench.stats import percentile


def read(ctx):
    st = [r.stats["admit_stall_s"] * 1e3 for r in ctx["served"]]
    return percentile(st, 90) if st else None
