"""Set-up seconds: process start to the window's start (loading, weights,
warm-up, compilation), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
