"""Plane and line rows the VM encode gathered per marched sample: the
field's rows a point on each side (``engine_stats()``'s
``encode_gather_rows_per_sample`` and ``color_gather_rows_per_sample``)
times the march's density samples (``samples_processed``) and color
anchors (``color_samples`` less the probe's), over the density samples.
None where the program does not count them."""


def read(ctx):
    st, frames = ctx["engine_stats"], [r.stats for r in ctx["served"]]
    dens = st.get("encode_gather_rows_per_sample")
    col = st.get("color_gather_rows_per_sample")
    marched = sum(s["samples_processed"] for s in frames)
    if dens is None or col is None or not marched or any(
            "color_samples" not in s for s in frames):
        return None
    anchors = sum(s["color_samples"] - s["probe_samples"] for s in frames)
    return (dens * marched + col * anchors) / marched
