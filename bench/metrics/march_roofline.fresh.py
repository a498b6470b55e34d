"""The march's share of its roofline, in %: the least time the chip could
take for the samples marched (the larger of gathered bytes over peak HBM
bandwidth and FLOPs over peak bf16 FLOP/s, at published widths, from
bench/work.py) over the march executable's device time in the trace."""
from bench import work


def read(ctx):
    peak = ctx["peak"]
    m = ctx["system"].march_module(ctx)
    if not m or not m["seconds"] or peak is None:
        return None
    samples = sum(r.stats["samples_processed"] for r in ctx["served"])
    least, _bound = work.march_least_seconds(ctx["cfg"], samples, peak)
    return 100.0 * least / m["seconds"]
