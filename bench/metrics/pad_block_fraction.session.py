"""Pad blocks over all blocks launched in the window's march batches (the
program's ``engine_stats()["pad_block_fraction"]``)."""


def read(ctx):
    st = ctx["engine_stats"]
    return st["pad_block_fraction"] if st.get("batches") else None
