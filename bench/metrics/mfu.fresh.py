"""Whole-frame model FLOP utilization, in %: FLOPs of every sample the
march and the probes ran in the window (published widths, bench/work.py)
over the traced window times the chip's peak bf16 FLOP/s."""
from bench import work


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if not tr or peak is None:
        return None
    w = work.per_sample(ctx["cfg"])
    flops = sum(r.stats["samples_processed"] * w["march_flops"]
                + r.stats["probe_samples"] * w["probe_flops"] for r in ctx["served"])
    return 100.0 * flops / (tr["window_s"] * peak["bf16_flops"])
