"""Hash-table rows the proposal sampler gathers a ray, over both proposal
fields: one at each level a field serves from corner rows, 8 at every
other, times its samples (``engine_stats()``'s
``proposal_gather_rows_per_ray``).  None where the program does not
count them."""


def read(ctx):
    rows = ctx["engine_stats"].get("proposal_gather_rows_per_ray")
    return None if rows is None else float(rows)
