"""90th-percentile frame latency in ms over every frame due in the
window, counted from the moment it was due (host clock); a frame never
served counts as infinitely late."""
from bench.stats import percentile


def read(ctx):
    late = ctx["latency_s"] + [float("inf")] * (len(ctx["requests"]) - len(ctx["served"]))
    return percentile(late, 90) * 1e3 if late else None
