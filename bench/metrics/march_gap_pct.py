"""Share of the window in which the engine had no march batch in flight,
in % (the program's ``engine_stats()["march_gap_s"]``: host time from
entry, and from each round's last collect, to the next first launch,
less the open-loop sleeps); None where the program does not count it."""


def read(ctx):
    gap, window = ctx["engine_stats"].get("march_gap_s"), ctx["window_host_s"]
    return 100.0 * gap / window if gap is not None and window > 0 else None
