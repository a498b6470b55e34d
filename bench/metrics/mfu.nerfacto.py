"""Whole-frame model FLOP utilization of the nerfacto field, in %: FLOPs
of every sample the march and the proposal sampler ran in the window
(published widths, bench/work_nerfacto.py) over the traced window times
the chip's peak bf16 FLOP/s."""
from bench import work_nerfacto


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if not tr or peak is None:
        return None
    w = work_nerfacto.per_sample(ctx["cfg"])
    flops = sum(r.stats["samples_processed"] * w["march_flops"]
                + r.stats["probe_samples"] * w["proposal_flops"] for r in ctx["served"])
    return 100.0 * flops / (tr["window_s"] * peak["bf16_flops"])
