"""The proposal sampler's share of its roofline, in %: the least time the
chip could take for the window's proposal samples (the larger of the
proposal grids' gathered bytes over peak HBM bandwidth and their FLOPs
over peak bf16 FLOP/s, at published widths, bench/work_nerfacto.py) over
the proposal executable's device time in the trace."""
from bench import work_nerfacto


def read(ctx):
    peak = ctx["peak"]
    m = ctx["system"].proposal_module(ctx)
    if not m or not m["seconds"] or peak is None:
        return None
    samples = sum(r.stats["probe_samples"] for r in ctx["served"])
    least, _bound = work_nerfacto.proposal_least_seconds(ctx["cfg"], samples, peak)
    return 100.0 * least / m["seconds"]
