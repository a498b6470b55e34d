"""Frames whose Stage A reused or skipped the probe, over frames served."""


def read(ctx):
    st = [r.stats for r in ctx["served"]]
    return sum(bool(s["probe_reused"] or s["probe_skipped"]) for s in st) / len(st) if st else None
