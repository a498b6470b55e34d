"""90th percentile over served frames of the time from the end of a
frame's admission to the delivery of its last block, in ms (the
program's per-frame ``march_s``); None where the program does not report
it."""
from bench.stats import percentile


def read(ctx):
    m = [r.stats["march_s"] * 1e3 for r in ctx["served"] if "march_s" in r.stats]
    return percentile(m, 90) if m else None
