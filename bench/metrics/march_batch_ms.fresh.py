"""Device ms of one pooled march batch: the march executable's time in
the trace over its launches (checked against the engine's batches)."""


def read(ctx):
    m = ctx["system"].march_module(ctx)
    return m["seconds"] / m["launches"] * 1e3 if m else None
