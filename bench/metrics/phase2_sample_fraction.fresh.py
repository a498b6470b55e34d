"""Stage A's effect: samples the march processed over the fixed-budget
baseline (ns_full a ray), summed over the window's frames (the program's
per-frame counters)."""


def read(ctx):
    st = [r.stats for r in ctx["served"]]
    base = sum(s["baseline_samples"] for s in st)
    return sum(s["samples_processed"] for s in st) / base if base else None
