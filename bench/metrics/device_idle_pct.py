"""Share of the traced window in which no operation ran on the device, in %."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None
