"""Device ms of Stage A's proposal sampler a frame: the proposal
executable's time in the trace (``jit_proposal_edges``) over its
launches, read only where they equal the frames it prepared."""


def read(ctx):
    m = ctx["system"].proposal_module(ctx)
    return m["seconds"] / m["launches"] * 1e3 if m else None
