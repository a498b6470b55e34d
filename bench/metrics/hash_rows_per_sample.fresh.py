"""Hash-table rows the encode gathers a marched point: one at each level
the field serves from corner rows, 8 at every other
(``engine_stats()``'s ``encode_gather_rows_per_sample``).  None where the
program does not count them."""


def read(ctx):
    rows = ctx["engine_stats"].get("encode_gather_rows_per_sample")
    return None if rows is None else float(rows)
